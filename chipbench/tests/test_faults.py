"""A whole run of a cell on the CPU, past the harness's look for a chip,
with the timed path sound and then broken underneath: ``correct`` must
come out true for the sound path and false for each fault the cell can
have — a round that returns its state unchanged, half of each agent's
batch left out with the loss taken over the rest, and (the paper's cell)
a round credited to a satellite that took no part.  (The cells run on
one chip, so there is no exchange between chips to leave out.)

The language-model cell runs a small decoder of the same layout
(``testdata/tiny-decoder.json``) on short sequences, judged by the
numbers of a language-model cell; the paper's cell runs as configured.
"""
import argparse
import json
import shutil
import time

import pytest

from chipbench import harness


def _tiny_root(tmp_path):
    here = tmp_path / "chipbench"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(here / "testdata" / "tiny-decoder.json",
                here / "configs" / "tiny-decoder.json")
    (here / "traffic" / "tiny_local.json").write_text(json.dumps(
        {"agents": 2, "batch": 2, "seq": 128, "n_epochs": 2, "pool_rounds": 4,
         "ahead_seconds": 0.1}))
    # the compared numbers of a language-model cell, with the loss among
    # them: the tiny decoder's half-batch fault shows in its loss
    (here / "limits" / "tiny-local.json").write_text(json.dumps({"limits": {
        "loss_gap": 7.5e-4, "dx1_gap": 4.0, "dx2_gap": 2.5, "cup2_gap": 0.2,
        "yhat2_gap": 1.5, "cdown2_gap": 0.45}}))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "tiny-decoder", "source": "test",
                             "file": "chipbench/configs/tiny-decoder.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-local", "config": "tiny-decoder",
                               "traffic": "tiny_local", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm2-local" in m.get("workloads", []):
            m["workloads"].append("tiny-local")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, here


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, fault):
    args = argparse.Namespace(workload=cell, seed=2 ** 33 + 17, seconds=0.3,
                              trace=0)
    return harness.run_cell(args, time.perf_counter(), here=root[1],
                            root=root[0], require_tpu=False,
                            driver_kw={"fault": fault})


@pytest.mark.parametrize("cell", ["tiny-local", "walker-kiruna-paper"])
@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_correct_only_when_the_path_is_sound(root, cell, fault):
    result = _run(root, cell, fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}


def test_a_wrong_participant_is_not_correct(root):
    """The engine's answer altered where it is produced: one delivery per
    round credited to a satellite that took no part."""
    result = _run(root, "walker-kiruna-paper", "wrong_participant")
    assert result["correct"] is False
    assert result["checks"]["rule_faults"]["value"] >= 1
    assert result["checks"]["rule_faults"]["limit"] == 0


@pytest.mark.parametrize("cell", ["tiny-local", "walker-kiruna-paper"])
def test_the_control_is_not_correct(root, cell):
    """The reference computed one precision below the configuration's,
    put in the program's place, fails the cell's limits."""
    from chipbench.compare import judge
    tmp, here = root
    bench = harness.load_benchmark(tmp)
    work, entry = harness.find_cell(bench, cell)
    config = harness.load_config(entry, tmp)
    traffic = harness.load_traffic(work["traffic"], here)
    limits = harness.load_limits(cell, here)
    drv = harness.load_module("drivers", config["driver"], here).Driver(
        config, traffic, 2 ** 33 + 29, lambda m: None)
    drv.setup()
    drv.release()
    ref = drv.reference()
    assert judge(drv.numbers(ref), limits)[0]
    assert not judge(drv.numbers(ref, prog=drv.reference(control=True)), limits)[0]
