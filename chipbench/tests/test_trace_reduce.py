"""The trace reduction against a small trace recorded on a TPU v5e.

``testdata/round4.xplane.pb`` holds four runs, inside ``chipbench.window``,
of a small program laid out like the deploy round: a scanned local
training under ``fedlt.local_train``, the fused uplink kernel under
``fedlt.uplink``/``fedlt.uplink.fused_pipeline``, then ``fedlt.aggregate``
and ``fedlt.downlink``.  ``round4.hlo.txt.gz`` is that program's compiled
HLO text.  The expected numbers are recomputed here from the raw events.
The device's clock in this trace runs about a millisecond behind the
host's, so on the raw clocks the first run's operations seem to start
before the window that holds them.
"""
import gzip

import pytest

from chipbench import harness, trace_reduce

DATA = harness.HERE / "testdata"


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "round4.xplane.pb"))
    window = [e for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name == trace_reduce.WINDOW]
    assert len(window) == 1
    w0 = window[0].start_ns
    w1 = w0 + window[0].duration_ns
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for p in pd.planes if p.name == "/device:TPU:0"
           for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    return (w0, w1), ops


@pytest.fixture(scope="module")
def hlo():
    with gzip.open(DATA / "round4.hlo.txt.gz", "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def summary(hlo):
    return trace_reduce.reduce_file(DATA / "round4.xplane.pb", [hlo])


def _union(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_window_busy_and_idle(raw, summary):
    (w0, w1), ops = raw
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    # on the raw clocks the first run starts before the window ...
    assert min(s for s, _, _ in ops) < w0
    # ... and once moved onto the host's clock every run lies inside it
    assert all(w0 * 1e-9 <= o.start and o.end <= w1 * 1e-9 for o in summary.ops)
    busy = _union((s, e) for s, e, _ in ops) * 1e-9
    assert summary.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0.0 < summary.busy_s < summary.window_s


def test_scopes_from_the_compiled_program(raw, summary):
    _, ops = raw
    # the scanned local training is one loop instruction per run: its
    # event holds the loop body's events
    loops = [e - s for s, e, name in ops if name.startswith("%while")]
    assert len(loops) == 4
    assert summary.scope_s("fedlt.local_train") == pytest.approx(sum(loops) * 1e-9)
    up = summary.scope_s("fedlt.uplink")
    assert up == pytest.approx(summary.scope_s("fedlt.uplink.fused_pipeline"))
    assert up > 0 and summary.scope_s("fedlt.aggregate") > 0
    assert summary.scope_s("fedlt.downlink") > 0
    scoped = sum(summary.scope_s(s) for s in ("fedlt.local_train", "fedlt.uplink",
                                                "fedlt.aggregate", "fedlt.downlink"))
    assert scoped <= summary.busy_s * (1 + 1e-9)
    assert summary.scope_s("fedlt.local") == 0.0      # whole names only


def test_kernel_time(raw, summary):
    _, ops = raw
    calls = [e - s for s, e, name in ops if name.startswith("%quant_pipeline")
             and "custom-call(" in name]
    assert len(calls) == 4
    assert summary.kernel_s("quant_pipeline") == pytest.approx(sum(calls) * 1e-9)
    assert summary.kernel_s("quant_pipeline") < summary.scope_s("fedlt.uplink")
    assert summary.kernel_s("pack_bits") == 0.0


def test_without_the_program_no_scope_is_found():
    bare = trace_reduce.reduce_file(DATA / "round4.xplane.pb")
    assert bare.scope_s("fedlt.local_train") == 0.0
    assert bare.kernel_s("quant_pipeline") > 0.0
    ctx = harness.Context(setup_s=1.0, window_s=1.0, round_times=[0.1] * 4,
                          counts={}, peaks={}, trace=bare)
    assert harness.load_module("metrics", "local_train_ms").read(ctx) is None


def test_breakdown(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fedlt.local_train:while"
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    idle = sum(s for _, s in summary.gaps())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert all(isinstance(label, str) and label for label, _ in b["idle_gaps"])


def test_metrics_read_from_the_trace(summary):
    ctx = harness.Context(setup_s=1.0, window_s=summary.window_s,
                          round_times=[summary.window_s / 4] * 4,
                          counts={"round_flops": 1e9,
                                  "quant_pipeline_bytes": 1024 * 1024},
                          peaks={"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9}, trace=summary)
    read = lambda name: harness.load_module("metrics", name).read(ctx)
    assert read("local_train_ms") == pytest.approx(
        1e3 * summary.scope_s("fedlt.local_train") / 4)
    assert read("idle_share.lm") == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))
    roof = read("quant_pipeline_roofline")
    assert roof == pytest.approx(100 * 4 * 1024 * 1024 / 819e9
                                 / summary.kernel_s("quant_pipeline"))
    assert 0 < roof <= 105
