"""repro.obs: tracer semantics, serialization round-trips, fast-vs-oracle
trace-diff (zero divergence on the equivalence scenarios), invariant
checking, metrics, Chrome export, and the CLI."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.constellation.links import message_bytes
from repro.obs.summary import DIFF_KINDS, of_kind
from repro.sim import Engine, get_scenario
from repro.sim.engine import Delivery, RoundResult

MSG = message_bytes(10000, 10.0)

# the fast-vs-oracle equivalence scenarios trace-diff must clear (ISSUE 6
# acceptance): lossless baseline, station contention, and every lossy
# channel family — flat-erasure ARQ, rain fade, degraded Ka-band budget,
# conjunction blackouts
DIFF_SCENARIOS = ["walker-kiruna", "dual-station", "lossy-uplink",
                  "rain-fade", "ka-band-degraded", "conjunction-outage"]


def _trace_run(scenario: str, fast: bool, *, rounds=2, async_n=15,
               seed=3):
    eng = Engine(get_scenario(scenario), seed=seed, fast=fast)
    with obs.tracing(scenario=scenario) as trc:
        t = 0.0
        for _ in range(rounds):
            t += eng.run_round(t, MSG).duration
        if async_n:
            eng.run_async(t, MSG, async_n)
        return trc.records()


# ---------------------------------------------------------------------------
# serialization round-trips (satellite a)
# ---------------------------------------------------------------------------

def test_delivery_roundtrip_json_stable():
    d = Delivery(sat=7, t_done=120.5, t_start=90.0, gateway=7, station=1,
                 hops=2, nbytes=1000.0, window=80.0,
                 nbytes_attempted=1250.0, retries=3, delivered=True)
    back = Delivery.from_dict(json.loads(json.dumps(d.to_dict())))
    assert back == d


def test_delivery_nan_window_maps_to_none():
    d = Delivery(sat=0, t_done=1.0, t_start=0.0, gateway=0, station=0,
                 hops=0, nbytes=0.0, delivered=False)
    enc = d.to_dict()
    assert enc["window"] is None
    json.dumps(enc, allow_nan=False)       # strict-JSON safe
    back = Delivery.from_dict(enc)
    assert math.isnan(back.window)
    assert back.delivered is False


def test_round_result_roundtrip():
    eng = Engine(get_scenario("lossy-uplink"), seed=3)
    res = eng.run_round(0.0, MSG)
    assert res.deliveries, "scenario produced no deliveries"
    back = RoundResult.from_dict(json.loads(json.dumps(res.to_dict())))
    assert back.deliveries == res.deliveries
    np.testing.assert_array_equal(back.mask, res.mask)
    np.testing.assert_array_equal(back.scheduled, res.scheduled)
    assert (back.duration, back.t0) == (res.duration, res.t0)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_none():
    assert obs.active() is None


def test_tracer_stack_nests():
    outer = obs.enable()
    inner = obs.enable()
    assert obs.active() is inner
    obs.disable()
    assert obs.active() is outer
    obs.disable()
    assert obs.active() is None


def test_tracing_flush_load_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.tracing(path, scenario="unit") as trc:
        trc.event("round", round=0, t0=0.0, duration=1.0, n_scheduled=1,
                  n_delivered=1, n_lost=0, bytes_air=10.0, engine="fast")
        trc.metrics.counter("bytes_air").add(10.0, station=0)
    records = obs.load(path)
    assert records[0]["kind"] == "header"
    assert records[0]["scenario"] == "unit"
    assert of_kind(records, "round")[0]["bytes_air"] == 10.0
    [m] = of_kind(records, "metrics")
    assert m["counters"]["bytes_air"]["total"] == 10.0


def test_span_records_host_timing():
    with obs.tracing() as trc:
        with trc.span("stage", name="work"):
            pass
        [rec] = trc.events
    assert rec["kind"] == "stage" and rec["name"] == "work"
    assert rec["dur_host"] >= 0.0 and rec["t_host"] >= 0.0


# ---------------------------------------------------------------------------
# series records, gzip traces, streaming flush (schema v2)
# ---------------------------------------------------------------------------

def test_series_records_and_extraction():
    from repro.obs.summary import extract_series
    with obs.tracing() as trc:
        trc.series("e_K", 1, 0.5)
        trc.series("e_K", 0, 1.0)          # out of order on purpose
        trc.series("bytes_up", 0, 128.0, station=0)
        records = trc.records()
    series = extract_series(records)
    assert series["e_K"] == {"steps": [0, 1], "values": [1.0, 0.5]}
    assert series["bytes_up"]["values"] == [128.0]
    # labelled fields survive on the raw record
    [b] = [r for r in records if r.get("name") == "bytes_up"]
    assert b["station"] == 0


def test_series_stays_out_of_diff_contract():
    # series curves carry error values that legitimately differ between
    # equivalent engine configurations — they must never break the
    # fast-vs-oracle diff
    assert "series" not in DIFF_KINDS
    ra = _trace_run("walker-kiruna", fast=True)
    rb = [dict(r) for r in ra]
    rb.append({"kind": "series", "name": "e_K", "step": 0, "value": 1.0})
    equal, _ = obs.diff(ra, rb)
    assert equal


def test_gzip_trace_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl.gz")
    with obs.tracing(path, scenario="unit") as trc:
        trc.event("round", round=0, t0=0.0, duration=1.0, n_scheduled=1,
                  n_delivered=1, n_lost=0, bytes_air=10.0, engine="fast")
        trc.series("e_K", 0, 2.5)
        trc.metrics.counter("bytes_air").add(10.0)
    raw = open(path, "rb").read()
    assert raw[:2] == b"\x1f\x8b", "not gzip-compressed on disk"
    records = obs.load(path)
    assert records[0]["kind"] == "header"
    assert of_kind(records, "series")[0]["value"] == 2.5
    [m] = of_kind(records, "metrics")
    assert m["counters"]["bytes_air"]["total"] == 10.0


def test_gzip_cli_subcommands(tmp_path, capsys):
    from repro.obs.__main__ import main
    pa = str(tmp_path / "a.jsonl.gz")
    eng = Engine(get_scenario("walker-kiruna"), seed=0)
    with obs.tracing(pa):
        eng.run_round(0.0, MSG)
    assert main(["summarize", pa]) == 0
    assert "round" in capsys.readouterr().out
    assert main(["check", pa]) == 0
    assert main(["diff", pa, pa]) == 0
    capsys.readouterr()


def test_streaming_flush_bounded_memory(tmp_path):
    path = str(tmp_path / "s.jsonl")
    with obs.tracing(path, stream_every=5, scenario="stream") as trc:
        for k in range(17):
            trc.event("round", round=k, t0=0.0, duration=1.0,
                      n_scheduled=0, n_delivered=0, n_lost=0,
                      bytes_air=0.0, engine="fast")
            assert len(trc.events) < 5          # buffer stays bounded
        trc.metrics.counter("bytes_air").add(1.0)
    records = obs.load(path)
    assert records[0]["kind"] == "header" and records[0]["streamed"]
    assert [r["round"] for r in of_kind(records, "round")] == list(range(17))
    # metrics snapshot semantics kept: exactly one, last, complete
    assert records[-1]["kind"] == "metrics"
    assert records[-1]["counters"]["bytes_air"]["total"] == 1.0
    assert sum(r["kind"] == "metrics" for r in records) == 1


def test_streaming_flush_gzip_and_partial_visibility(tmp_path):
    path = str(tmp_path / "s.jsonl.gz")
    with obs.tracing(path, stream_every=2) as trc:
        for k in range(4):
            trc.event("round", round=k, t0=0.0, duration=1.0,
                      n_scheduled=0, n_delivered=0, n_lost=0,
                      bytes_air=0.0, engine="fast")
        trc.flush()
    records = obs.load(path)
    assert len(of_kind(records, "round")) == 4


def test_streaming_without_path_rejected():
    with pytest.raises(ValueError):
        obs.Tracer(stream_every=10)


def test_summarize_dict_machine_readable():
    from repro.obs.summary import summarize_dict
    records = _trace_run("lossy-uplink", fast=True, rounds=2)
    s = summarize_dict(records)
    assert s["schema"] == 2
    assert s["meta"]["scenario"] == "lossy-uplink"
    assert s["round_kind"] == "round" and s["n_rounds"] == 2
    assert s["deliveries"]["n"] == len(of_kind(records, "delivery"))
    assert s["final"]["bytes_air"] == \
        sum(r["bytes_air"] for r in of_kind(records, "round"))
    assert "bytes_air" in s["series"]
    json.dumps(s, allow_nan=False)      # strict-JSON machine output


def test_cli_summarize_json(tmp_path, capsys):
    from repro.obs.__main__ import main
    pa = str(tmp_path / "a.jsonl")
    eng = Engine(get_scenario("walker-kiruna"), seed=0)
    with obs.tracing(pa):
        eng.run_round(0.0, MSG)
    assert main(["summarize", pa, "--json"]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_rounds"] == 1 and s["round_kind"] == "round"


# ---------------------------------------------------------------------------
# schema-v1 compatibility (committed fixture)
# ---------------------------------------------------------------------------

V1_FIXTURE = __file__.rsplit("/", 1)[0] + "/data/trace_schema_v1.jsonl"


def test_v1_fixture_still_loads_and_summarizes():
    from repro.obs.summary import summarize_dict
    records = obs.load(V1_FIXTURE)
    assert records[0]["schema"] == 1
    text = obs.summarize(records)
    assert "round" in text
    assert obs.check(records) == []
    s = summarize_dict(records)
    assert s["round_kind"] == "fl_round" and s["n_rounds"] == 2
    # v1 has no series records: the federated curves are synthesized
    # from the fl_round records so ledger/convgate read old traces too
    assert s["series"]["e_K"]["values"] == [24.25, 21.5]
    assert s["series"]["bytes_up"]["values"] == [2112.0, 4224.0]
    assert s["final"]["e_K"] == 21.5


def test_v1_fixture_diffs_against_itself():
    records = obs.load(V1_FIXTURE)
    equal, report = obs.diff(records, records)
    assert equal, report


# ---------------------------------------------------------------------------
# engine emission + fast-vs-oracle trace-diff (the tentpole contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", DIFF_SCENARIOS)
def test_trace_diff_fast_vs_oracle_zero_divergence(scenario):
    ra = _trace_run(scenario, fast=True)
    rb = _trace_run(scenario, fast=False)
    equal, report = obs.diff(ra, rb)
    assert equal, report
    # the engine tag is the one legitimate difference
    assert of_kind(ra, "round")[0]["engine"] == "fast"
    assert of_kind(rb, "round")[0]["engine"] == "oracle"
    # and both traces satisfy the invariants
    assert obs.check(ra) == []
    assert obs.check(rb) == []


def test_trace_diff_localizes_divergence():
    ra = _trace_run("walker-kiruna", fast=True)
    rb = [dict(r) for r in ra]
    victims = of_kind(rb, "delivery")
    victims[2]["t_done"] += 1.0
    equal, report = obs.diff(ra, rb)
    assert not equal
    assert "t_done" in report and "DIVERGED" in report


def test_trace_diff_detects_missing_records():
    ra = _trace_run("walker-kiruna", fast=True)
    # a truncated trace (final async_run summary missing): every zipped
    # pair still matches, so only the count comparison can catch it
    rb = [r for r in ra if r.get("kind") != "async_run"]
    equal, report = obs.diff(ra, rb)
    assert not equal and "counts differ" in report
    assert "async_run" in report


def test_check_catches_bytes_violation():
    records = [dict(r) for r in _trace_run("walker-kiruna", fast=True)]
    of_kind(records, "round")[0]["bytes_air"] += 1.0
    bad = obs.check(records)
    assert any("bytes conservation" in m for m in bad)


def test_check_catches_failed_delivery_with_payload():
    records = [{"kind": "delivery", "round": None, "sat": 0, "t_done": 1.0,
                "t_start": 0.0, "delivered": False, "nbytes": 5.0,
                "nbytes_attempted": 5.0, "retries": 0}]
    assert any("failed but carries" in m for m in obs.check(records))


def test_lossy_trace_has_arq_and_retx_metrics():
    records = _trace_run("lossy-uplink", fast=True, rounds=3)
    arq = of_kind(records, "arq")
    assert arq, "lossy-uplink produced no ARQ events"
    [m] = of_kind(records, "metrics")
    assert m["counters"]["bytes_retx"]["total"] > 0.0
    assert m["histograms"]["delivery_latency"]["count"] == \
        len(of_kind(records, "delivery"))


def test_round_indices_and_async_runs_advance():
    eng = Engine(get_scenario("walker-kiruna"), seed=0)
    with obs.tracing() as trc:
        eng.run_round(0.0, MSG)
        eng.run_round(500.0, MSG)
        eng.run_async(0.0, MSG, 5)
        eng.run_async(0.0, MSG, 5)
        records = trc.records()
    assert [r["round"] for r in of_kind(records, "round")] == [0, 1]
    assert [r["run"] for r in of_kind(records, "async_run")] == [0, 1]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_total():
    m = obs.Metrics()
    c = m.counter("bytes")
    c.add(10.0, station=0)
    c.add(5.0, station=1)
    c.add(1.0, station=0)
    assert c.total == 16.0
    d = m.to_dict()["counters"]["bytes"]
    assert d["total"] == 16.0
    assert d["cells"]["station=0"] == 11.0


def test_histogram_stats_and_bounds():
    h = obs.Metrics().histogram("lat", bounds=(1.0, 10.0))
    for v in (0.5, 2.0, 20.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 3 and d["min"] == 0.5 and d["max"] == 20.0
    assert d["counts"] == [1, 1, 1]
    assert abs(d["mean"] - 7.5) < 1e-9
    # without a lower bound nothing underflows; above-range samples are
    # surfaced as the explicit overflow count (= the last bucket)
    assert d["lo"] is None and d["underflow"] == 0
    assert d["overflow"] == 1


def test_histogram_underflow_and_overflow_explicit():
    h = obs.Metrics().histogram("stale", bounds=(1.0, 10.0), lo=0.0)
    for v in (-2.0, -1.0, 0.5, 5.0, 100.0, 200.0):
        h.observe(v)
    d = h.to_dict()
    # below-lo samples are tallied, not folded into the first bucket
    assert d["underflow"] == 2
    assert d["overflow"] == 2 and d["counts"] == [1, 1, 2]
    assert d["counts"][0] == 1          # only the in-range 0.5
    # sidecar stats still describe EVERY observation
    assert d["count"] == 6 and d["min"] == -2.0 and d["max"] == 200.0
    assert d["sum"] == -2.0 - 1.0 + 0.5 + 5.0 + 100.0 + 200.0


def test_engine_latency_histogram_has_lower_bound():
    records = _trace_run("lossy-uplink", fast=True, rounds=2)
    [m] = of_kind(records, "metrics")
    lat = m["histograms"]["delivery_latency"]
    assert lat["lo"] == 0.0 and lat["underflow"] == 0
    assert "overflow" in lat


# ---------------------------------------------------------------------------
# SpaceRunner + kernels emission
# ---------------------------------------------------------------------------

def _small_runner(channel=None, **kw):
    from repro.channel import ChannelModel, SelectiveRepeatARQ
    from repro.constellation.orbits import GroundStation, Walker
    from repro.core.compression import UniformQuantizer
    from repro.core.error_feedback import EFChannel
    from repro.core.fedlt import FedLT
    from repro.core.fedlt_sat import SpaceRunner
    from repro.data.logistic import generate, make_local_loss
    from repro.sim import Scenario
    n = 20
    data, _ = generate(jax.random.PRNGKey(0), n_agents=n, m=40, dim=16)
    loss = make_local_loss(eps=50.0, n_agents=n)
    q = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    alg = FedLT(loss=loss, n_epochs=2, gamma=0.005, rho=20.0,
                uplink=EFChannel(q), downlink=EFChannel(q))
    sc = Scenario(name="small", walker=Walker(n_sats=n, n_planes=4),
                  stations=(GroundStation(),), k_direct=3, n_relay=2)
    if channel == "lossy":
        channel = ChannelModel(loss=0.25,
                               arq=SelectiveRepeatARQ(seg_bytes=16,
                                                      max_rounds=2))
    runner = SpaceRunner(Engine(sc), compressor=q, channel=channel, **kw)
    return runner, alg, alg.init(jnp.zeros((16,)), n), data


def test_space_runner_sync_emits_fl_rounds_and_ef_reverts():
    runner, alg, st, data = _small_runner(channel="lossy")
    with obs.tracing() as trc:
        runner.run(alg, st, data, 6, jax.random.PRNGKey(2))
        records = trc.records()
    fl = of_kind(records, "fl_round")
    assert [r["round"] for r in fl] == list(range(6))
    assert all(r["mode"] == "sync" for r in fl)
    # cumulative ledger is monotone (also a check() invariant)
    ups = [r["bytes_up"] for r in fl]
    assert ups == sorted(ups) and ups[-1] > 0
    rev = of_kind(records, "ef_revert")
    assert rev and all(r["absorb"] for r in rev)
    assert all(r["resid_norm"] >= 0.0 for r in rev)
    assert sum(r["n_lost"] for r in rev) == \
        sum(r["n_lost"] for r in fl if r["n_lost"])
    assert obs.check(records) == []
    # host spans for both stages of every round
    stages = of_kind(records, "stage")
    assert sum(s["name"] == "runner.schedule" for s in stages) == 6
    assert sum(s["name"] == "runner.dispatch" for s in stages) == 6


def test_space_runner_async_emits_staleness():
    runner, alg, st, data = _small_runner(mode="async", buffer_size=4)
    with obs.tracing() as trc:
        runner.run(alg, st, data, 4, jax.random.PRNGKey(2))
        records = trc.records()
    fl = of_kind(records, "fl_round")
    assert fl and all(r["mode"] == "async" for r in fl)
    assert all(r["staleness"] >= 0.0 for r in fl)
    [m] = of_kind(records, "metrics")
    assert m["histograms"]["staleness"]["count"] == \
        sum(r["n_active"] for r in fl)
    assert obs.check(records) == []


def _captured_spans(log_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of every host event of the
    ``jax.profiler`` capture written under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData
    [path] = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)) for ev in line.events]
    return out


def _capture(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def test_runner_spans_land_in_a_profiler_capture(tmp_path):
    """With no Tracer, a sync ``Experiment.run`` under ``jax.profiler``
    shows one ``runner.round`` per round, carrying its index, around one
    ``runner.schedule`` (the engine's phases inside it) and one
    ``runner.dispatch``; and writes no trace record anywhere."""
    from repro.api import Experiment
    from repro.core.compression import UniformQuantizer
    from repro.core.error_feedback import EFChannel
    from repro.core.fedlt import FedLT
    from repro.data.logistic import generate, make_local_loss
    q = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    data, _ = generate(jax.random.PRNGKey(0), n_agents=100, m=16, dim=12)
    alg = FedLT(loss=make_local_loss(eps=50.0, n_agents=100), n_epochs=1,
                gamma=0.005, rho=20.0, uplink=EFChannel(q),
                downlink=EFChannel(q))
    exp = Experiment("walker-kiruna", alg, compressor=q)
    st = exp.init(jnp.zeros((12,)), 100)
    assert obs.active() is None
    with _capture(tmp_path / "prof"):
        exp.run(st, data, 3, jax.random.PRNGKey(1)).state.x.block_until_ready()
    assert obs.active() is None
    assert not list(tmp_path.rglob("*.jsonl*"))
    spans = _captured_spans(tmp_path / "prof")
    rounds = sorted((s for s in spans if s[0] == "runner.round"),
                    key=lambda s: s[1])
    assert [s[3].get("round") for s in rounds] == [0, 1, 2]

    def inside(name, outer):
        return [s for s in spans
                if s[0] == name and outer[1] <= s[1] and s[2] <= outer[2]]

    for r in rounds:
        [sched] = inside("runner.schedule", r)
        [_] = inside("runner.dispatch", r)
        assert inside("engine.assign", sched)
        assert inside("engine.event_loop", sched)
        assert not inside("runner.revert", r)     # a lossless scenario


def test_span_records_only_under_a_tracer():
    with obs.span("work", round=3):
        pass
    with obs.tracing() as trc:
        with obs.span("work", round=3):
            pass
        with obs.span("engine.assign", phase="assign"):
            pass
        [rec] = trc.events
        prof = dict(trc.prof._acc)
    assert rec["kind"] == "stage" and rec["name"] == "work"
    assert rec["round"] == 3 and rec["dur_host"] >= 0.0
    # a phase span feeds the phase profiler, not the record stream
    assert list(prof) == [("assign",)] and prof[("assign",)][0] == 1


def test_tracer_span_and_span_share_the_profiler_name(tmp_path):
    with _capture(tmp_path):
        with obs.tracing() as trc:
            with trc.span("stage", name="work", round=1):
                pass
            with obs.span("other"):
                pass
            names = [r["name"] for r in trc.events]
    captured = {s[0]: s[3] for s in _captured_spans(tmp_path)}
    assert names == ["work", "other"]
    assert captured["work"] == {"round": 1} and "other" in captured


def test_kernel_dispatch_events():
    from repro.kernels import ops
    x = jnp.arange(65536, dtype=jnp.uint32) % 16
    with obs.tracing() as trc:
        words = ops.pack_bits(x, 4)
        ops.unpack_bits(words, 4, x.size)
        records = trc.records()
    names = [k["name"] for k in of_kind(records, "kernel")]
    assert names == ["pack_bits", "unpack_bits"]
    [m] = of_kind(records, "metrics")
    cells = m["counters"]["kernel_dispatches"]["cells"]
    assert cells == {"name=pack_bits": 1.0, "name=unpack_bits": 1.0}


def test_kernel_untraced_path_unchanged():
    from repro.kernels import ops
    x = jnp.arange(65536, dtype=jnp.uint32) % 16
    baseline = np.asarray(ops.pack_bits(x, 4))
    with obs.tracing():
        traced = np.asarray(ops.pack_bits(x, 4))
    np.testing.assert_array_equal(baseline, traced)


def test_link_events_only_on_budget_channels():
    # rain-fade rides a LinkBudget → link events with elevation/fade;
    # lossy-uplink is flat-rate → fast path replays ArqPlans, no link kind
    budget = _trace_run("rain-fade", fast=True, async_n=0)
    flat = _trace_run("lossy-uplink", fast=True, async_n=0)
    links = of_kind(budget, "link")
    assert links
    assert all(l["elevation_deg"] > 0.0 and l["rate"] > 0.0 for l in links)
    assert of_kind(flat, "link") == []
    # link/outage kinds stay out of the diff contract
    assert "link" not in DIFF_KINDS and "outage" not in DIFF_KINDS


def test_outage_events_on_blackout_scenarios():
    records = _trace_run("conjunction-outage", fast=True, async_n=0)
    outs = of_kind(records, "outage")
    assert outs and any(o["n_blocked"] > 0 for o in outs)
    assert all(o["n_blocked"] <= o["n_windows"] for o in outs)


# ---------------------------------------------------------------------------
# chrome export + CLI
# ---------------------------------------------------------------------------

def test_chrome_trace_structure(tmp_path):
    records = _trace_run("lossy-uplink", fast=True, rounds=2)
    doc = obs.chrome_trace(records)
    ev = doc["traceEvents"]
    phases = {e["ph"] for e in ev}
    assert {"M", "X", "C"} <= phases
    slices = [e for e in ev if e["ph"] == "X" and e.get("cat") == "delivery"]
    assert len(slices) == len(of_kind(records, "delivery"))
    for e in ev:        # Perfetto needs numeric ts on every non-meta event
        if e["ph"] != "M":
            assert isinstance(e["ts"], float)
    out = str(tmp_path / "x.json")
    obs.write_chrome_trace(records, out)
    assert json.load(open(out))["traceEvents"]


def test_cli_summarize_diff_check_chrome(tmp_path, capsys):
    from repro.obs.__main__ import main
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    eng = Engine(get_scenario("walker-kiruna"), seed=0)
    with obs.tracing(pa):
        eng.run_round(0.0, MSG)
    eng2 = Engine(get_scenario("walker-kiruna"), seed=0, fast=False)
    with obs.tracing(pb):
        eng2.run_round(0.0, MSG)

    assert main(["summarize", pa]) == 0
    assert "round" in capsys.readouterr().out

    assert main(["diff", pa, pb]) == 0
    assert "identical" in capsys.readouterr().out

    assert main(["check", pa, pb]) == 0
    assert main(["--check", pa]) == 0          # the CI alias
    capsys.readouterr()

    assert main(["chrome", pa, "-o", str(tmp_path / "a.json")]) == 0
    assert json.load(open(tmp_path / "a.json"))["traceEvents"]
    capsys.readouterr()

    # a diverging pair exits 1 (same scenario, shifted round start —
    # walker-kiruna is lossless, so the seed alone can't shift it)
    eng3 = Engine(get_scenario("walker-kiruna"), seed=0)
    pc = str(tmp_path / "c.jsonl")
    with obs.tracing(pc):
        eng3.run_round(60.0, MSG)
    assert main(["diff", pa, pc]) == 1
    assert "DIVERGED" in capsys.readouterr().out

    # a tampered trace fails check with exit 1
    recs = obs.load(pa)
    for r in recs:
        if r.get("kind") == "round":
            r["bytes_air"] += 1.0
    pd = str(tmp_path / "d.jsonl")
    with open(pd, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert main(["check", pd]) == 1
    assert "violation" in capsys.readouterr().out
