#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights, data, compilation, the first rounds) is timed as
``setup_s``; then the cell's path runs for ``--seconds``.  With
``--trace 1`` the window runs under the profiler and the result carries
the cell's per-layer metrics, else its end-to-end metrics.  Afterwards
the program's state is freed and the plain reference decides
``correct``.  The last line of standard output is one JSON object; each
compared number is printed beside its limit there (``checks``) and as the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.harness import BenchError, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args, T_START)
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
