"""The harness on the CPU: what it finds by name, what it refuses, and
``BENCHMARK.json`` against the rules it is written to."""
import json
import os
import re
import shutil
import statistics
import subprocess
import sys

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_every_cell_finds_its_files_by_name(bench):
    for work in bench["workloads"]:
        w, cfg_entry = harness.find_cell(bench, work["name"])
        cfg = harness.load_config(cfg_entry)
        assert cfg["name"] == cfg_entry["name"]
        harness.load_traffic(w["traffic"])
        assert harness.load_limits(w["name"])
        assert hasattr(harness.load_module("drivers", cfg["driver"]), "Driver")
        for section in ("end_to_end", "per_layer"):
            for m in harness.metric_entries(bench, w["name"], section):
                assert callable(harness.load_module("metrics", m["name"]).read)


def test_a_cell_added_by_files_alone_is_found(tmp_path, bench):
    here = tmp_path / "chipbench"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "new-model.json").write_text(json.dumps(
        {"name": "new-model", "driver": "deploy_round"}))
    (here / "traffic" / "new_mix.json").write_text(json.dumps({"agents": 1}))
    (here / "limits" / "new-cell.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.5}}))
    (here / "metrics" / "new_metric.ms.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx.window_s\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "new-model", "source": "x",
                           "file": "chipbench/configs/new-model.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "new-cell", "config": "new-model",
                             "traffic": "new_mix", "chips": 1, "why": "x"})
    new["per_layer"].append({"name": "new_metric.ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "device", "moves": "round_s",
                             "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    loaded = harness.load_benchmark(tmp_path)
    w, entry = harness.find_cell(loaded, "new-cell")
    assert harness.load_config(entry, tmp_path)["driver"] == "deploy_round"
    assert harness.load_traffic(w["traffic"], here) == {"agents": 1}
    assert harness.load_limits("new-cell", here) == {"loss_gap": 0.5}
    entries = harness.metric_entries(loaded, "new-cell", "per_layer")
    assert [m["name"] for m in entries] == ["new_metric.ms"]
    ctx = harness.Context(setup_s=1.0, window_s=2.0, round_times=[1.0, 1.0],
                          counts={}, peaks={})
    assert harness.read_metrics(entries, ctx, here) == {
        "new_metric.ms": {"value": 2000.0, "unit": "ms"}}
    # the existing cells are untouched by the addition
    assert ([m["name"] for m in harness.metric_entries(loaded, "smollm2-local",
                                                       "per_layer")]
            == [m["name"] for m in harness.metric_entries(bench, "smollm2-local",
                                                          "per_layer")])


def test_unknown_names_are_errors(bench):
    with pytest.raises(harness.BenchError):
        harness.find_cell(bench, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.peaks_for("TPU v99")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "smollm2-local", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(harness.ROOT), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")


@pytest.mark.parametrize("q", [90, 95])
def test_percentile_is_pythons(q):
    values = [0.3, 0.31, 0.29, 0.5, 0.305, 0.302, 0.33, 0.28, 0.301, 0.299]
    assert harness.percentile(values, q) == statistics.quantiles(values, n=100)[q - 1]


def test_benchmark_json_keeps_to_its_rules(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = harness.load_config(c)
        assert cfg["reduced"] == c["reduced"]
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(cells)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    metric_names = list(e2e) + [m["name"] for m in bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:
        reported = harness.metric_entries(bench, cell, "end_to_end")
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
        assert harness.metric_entries(bench, cell, "per_layer")


def test_one_program_serves_every_seed():
    from chipbench import generate
    make = lambda s: generate.logistic_data(generate.seed_key(s), n_agents=2,
                                            m=3, dim=5)
    before = generate.logistic_data._cache_size()
    low, high = make(7), make(7 + 2 ** 40)
    assert generate.logistic_data._cache_size() == before + 1
    # seeds that agree below 32 bits still differ
    assert not (low["a"] == high["a"]).all()
