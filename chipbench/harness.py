"""What the benchmark finds by name, and the one run of one cell.

Everything that belongs to one configuration, traffic mix, path driver,
limit set or metric is a file of its own:

    configs/<config>.json    the configuration as it is run
    traffic/<traffic>.json   the mix's parameters
    limits/<cell>.json       the limit of each compared number
    drivers/<driver>.py      the path the configuration names
    metrics/<metric>.py      ``read(ctx)`` → a number, or None

so that a later cell, mix or metric is added by adding files and entries
of ``BENCHMARK.json`` alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(Exception):
    """The benchmark cannot run this cell here."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> tuple:
    """``(workload entry, config entry)`` of the cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    work = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return work, configs[work["config"]]


def load_config(entry: dict, root: Path = ROOT) -> dict:
    return read_json(root / entry["file"])


def load_traffic(name: str, here: Path = HERE) -> dict:
    return read_json(here / "traffic" / f"{name}.json")


def load_limits(cell: str, here: Path = HERE) -> dict:
    return read_json(here / "limits" / f"{cell}.json")["limits"]


def load_module(kind: str, name: str, here: Path = HERE):
    """The module ``<here>/<kind>/<name>.py`` (names may hold dots)."""
    path = here / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_entries(bench: dict, cell: str, section: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def peaks_for(kind: str, here: Path = HERE) -> dict:
    table = read_json(here / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


def enable_compile_cache(jax, root: Path = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one.  Every program is
    kept, however quickly it compiled, so a second run compiles
    nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(root / ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def percentile(values, q: int) -> float:
    """The ``q``-th percentile as Python's ``statistics.quantiles`` gives
    it (exclusive method, 100 cut points)."""
    values = list(values)
    if len(values) < 2:
        return float(values[0])
    return float(statistics.quantiles(values, n=100)[q - 1])


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    setup_s: float
    window_s: float
    round_times: list
    counts: dict
    peaks: dict
    trace: Optional[object] = None       # trace_reduce.Summary
    spans: dict = dataclasses.field(default_factory=dict)

    @property
    def rounds(self) -> int:
        return len(self.round_times)


def read_metrics(entries: list, ctx: Context, here: Path = HERE) -> dict:
    """Each metric that its reader finds something for, with its unit."""
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"], here).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class CompileCounter:
    """Counts the programs XLA compiles while it is open."""

    def __init__(self, jax):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **kw) -> None:
        if self.on and "backend_compile" in event:
            self.n += 1


class Window:
    """The measured window, opened by the driver when its first timed
    round starts: set-up ends there, compiles are counted from there,
    and in a traced run the host span ``chipbench.window`` opens."""

    def __init__(self, jax, t_start: float, counter: CompileCounter,
                 trace: bool):
        self.jax, self.t_start, self.counter, self.trace = jax, t_start, counter, trace
        self.setup_s = None
        self._span = None

    def start(self) -> float:
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        self.counter.on = True
        if self.trace:
            from chipbench.trace_reduce import WINDOW
            self._span = self.jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()
        return t

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self.counter.on = False


def log(msg: str) -> None:
    print(msg, flush=True)


def run_cell(args, t_start: float, here: Path = HERE,
             root: Path = ROOT, require_tpu: bool = True,
             driver_kw: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``require_tpu=False`` and ``driver_kw`` are for the harness's own
    tests, which drive a tiny cell on the CPU with the timed path broken
    on purpose."""
    import jax
    devices = jax.devices()
    log(f"JAX and the device up after {time.perf_counter() - t_start:.3f} s")
    dev = devices[0]
    bench = load_benchmark(root)
    work, cfg_entry = find_cell(bench, args.workload)
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {dev.platform!r}); "
                         f"this benchmark runs on the chip only")
    if len(devices) < work["chips"]:
        raise BenchError(f"the cell asks for {work['chips']} chips, JAX "
                         f"finds {len(devices)}")
    peaks, cache = {}, None
    if require_tpu:
        peaks = peaks_for(dev.device_kind, here)
        cache = enable_compile_cache(jax, root)
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}")

    config = load_config(cfg_entry, root)
    traffic = load_traffic(work["traffic"], here)
    limits = load_limits(work["name"], here)
    driver = load_module("drivers", config["driver"], here).Driver(
        config, traffic, args.seed, log, **(driver_kw or {}))
    counter = CompileCounter(jax)
    driver.setup()
    window = Window(jax, t_start, counter, bool(args.trace))
    summary = None
    if args.trace:
        # a traced run measures a shorter window of its own: the trace of
        # a whole window would take longer to write and read than a run
        # may last
        seconds = min(args.seconds, traffic.get("trace_seconds", args.seconds))
        from chipbench import trace_reduce
        trace_dir = root / ".chipbench_trace"
        trace_reduce.clear(trace_dir)
        # host spans from TraceAnnotation and JAX's runtime; no Python
        # function tracing, which would slow a host-bound path
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        times, window_s = driver.window(seconds, window.start)
        window.close()
        jax.profiler.stop_trace()
    else:
        times, window_s = driver.window(args.seconds, window.start)
        window.close()
    setup_s = window.setup_s
    log(f"set-up {setup_s:.3f} s")
    failed = driver.failures()
    log(f"window {window_s:.6f} s, {len(times)} rounds, {counter.n} "
        f"compiles inside it")
    for key, value in driver.counts.items():
        log(f"{key} {value}")
    if "tokens_per_round" in driver.counts:
        log(f"tokens per second {driver.counts['tokens_per_round'] * len(times) / window_s:.3f}")
    if args.trace:
        summary = trace_reduce.reduce_dir(trace_dir, driver.hlo_texts())
        trace_reduce.clear(trace_dir)
        log(f"trace: busy {summary.busy_s:.6f} s of {summary.window_s:.6f} s; "
            f"{len(summary.ops)} device ops")
    memory = driver.memory_bytes()
    driver.release()

    t_ref = time.perf_counter()
    numbers = driver.compare()
    from chipbench.compare import judge
    correct, checks = judge(numbers, limits)
    log(f"reference and comparison {time.perf_counter() - t_ref:.3f} s")
    if counter.n:
        correct = False
        checks["compiles_in_window"] = {"value": counter.n, "limit": 0}

    ctx = Context(setup_s=setup_s, window_s=window_s, round_times=times,
                  counts=driver.counts, peaks=peaks, trace=summary,
                  spans=getattr(driver, "spans", {}))
    section = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(metric_entries(bench, work["name"], section), ctx,
                           here)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory)}
    result = {"correct": bool(correct), "attempted": len(times),
              "failed": int(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result
