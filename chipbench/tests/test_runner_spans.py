"""The per-layer metrics that read the program's own round spans
(``runner.round`` and its ``runner.schedule`` and ``runner.dispatch``).

They split the traced window four ways, a round each: the engine's call,
the round's dispatch, the rest of the round, and the window outside every
round.  A traced run of the paper's cell on the CPU shows the four, and
that they add up to the window; a trace without the spans (a program that
lacks them) gives none of them.
"""
import argparse
import json
import shutil
import time

import pytest

from chipbench import harness, trace_reduce
from chipbench.trace_reduce import Span, Summary

METRICS = ("runner_schedule_ms", "runner_dispatch_ms", "runner_self_ms",
           "runner_unspanned_ms")


def _read(name, trace, rounds):
    ctx = harness.Context(setup_s=1.0, window_s=trace.window_s,
                          round_times=[0.1] * rounds, counts={}, peaks={},
                          trace=trace)
    return harness.load_module("metrics", name).read(ctx)


def test_spans_split_the_window_by_hand():
    # two rounds inside a 10 s window and one cut by each of its ends
    spans = [Span(-1.0, 1.0, "runner.round"), Span(-0.5, 0.5, "runner.schedule"),
             Span(2.0, 5.0, "runner.round"), Span(2.0, 3.0, "runner.schedule"),
             Span(3.5, 4.5, "runner.dispatch"), Span(3.6, 3.7, "DevicePut"),
             Span(6.0, 8.0, "runner.round"), Span(6.5, 7.0, "runner.dispatch"),
             Span(9.0, 11.0, "runner.round"), Span(9.5, 10.5, "runner.schedule")]
    trace = Summary(window=(0.0, 10.0), ops=[], spans=spans, n_devices=1)
    got = {m: _read(m, trace, 4) for m in METRICS}
    # schedule 0.5 + 1 + 0.5 s, dispatch 1 + 0.5 s, rounds 1 + 3 + 2 + 1 s,
    # so 3.5 s of the rounds' own and 3 s outside them
    assert got == pytest.approx({"runner_schedule_ms": 500.0,
                                 "runner_dispatch_ms": 375.0,
                                 "runner_self_ms": 875.0,
                                 "runner_unspanned_ms": 750.0})
    assert sum(got.values()) == pytest.approx(1e3 * 10.0 / 4)


@pytest.mark.parametrize("name", METRICS)
def test_no_runner_span_reads_nothing(name):
    trace = trace_reduce.reduce_file(harness.HERE / "testdata" / "round4.xplane.pb")
    assert trace.spans and not any(sp.name == "runner.round" for sp in trace.spans)
    assert _read(name, trace, 4) is None
    assert harness.load_module("metrics", name).read(harness.Context(
        setup_s=1.0, window_s=1.0, round_times=[0.1], counts={},
        peaks={})) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the paper's cell on the CPU, about 1 s long, in a
    copy of the benchmark (the trace is written beside it)."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(harness.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "BENCHMARK.json").write_text(json.dumps(harness.load_benchmark()))
    args = argparse.Namespace(workload="walker-kiruna-paper",
                              seed=2 ** 33 + 41, seconds=1.0, trace=1)
    return harness.run_cell(args, time.perf_counter(), here=root / "chipbench",
                            root=root, require_tpu=False)


def test_a_traced_paper_run_splits_its_window(traced):
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(METRICS) <= set(metrics), sorted(metrics)
    assert all(metrics[m] >= 0.0 for m in METRICS)
    per_round = 1e3 * traced["device"]["window_s"] / traced["attempted"]
    assert sum(metrics[m] for m in METRICS) == pytest.approx(per_round, rel=0.02)
    # the program's span holds the harness's own timing of the same call
    assert metrics["runner_schedule_ms"] >= 0.9 * metrics["engine_ms"]
    assert traced["correct"] is True
