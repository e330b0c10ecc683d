#!/usr/bin/env python3
"""Read the compared numbers of a cell on the chip, at the cell's own
sizes, for the program, for the control and for planted faults.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 \\
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3] \\
        [--witness-seeds 1] [--out FILE]

For each seed the program runs the cell's set-up (the same compiled path
and the same first rounds as a benchmark run, without the window) and is
compared with the reference.  The control is the reference computed in
the next precision below the configuration's (``reference(control=True)``
of the driver), put in the program's place.  Each fault the driver can
plant (its ``FAULTS`` but ``unchanged``, which reads 1 with no run) runs
on the fault seeds: ``half_batch`` leaves half of each agent's batch out
of the program's loss, and ``wrong_participant`` (the paper's cell)
credits a round's delivery to a satellite that took no part.  Each reading is a
JSON line; a cell's limits (``limits/<cell>.json``) are set from them:
above the largest program reading, below the smallest control or fault
reading.  The witnesses (language-model cells only) are the reference
kept in bfloat16 (``bf16``) and with the quantizer's arithmetic in
bfloat16 too (``bf16q``): they show which of the program's gaps come from
its own precision.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--witness-seeds", type=int, nargs="*", default=[],
                    help="also read the LM reference's bfloat16 witnesses")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    bench = harness.load_benchmark()
    work, entry = harness.find_cell(bench, args.workload)
    config = harness.load_config(entry)
    traffic = harness.load_traffic(work["traffic"])
    mod = harness.load_module("drivers", config["driver"])
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = list(dict.fromkeys(args.seeds + args.control_seeds + args.fault_seeds
                               + args.witness_seeds))
    for seed in seeds:
        t0 = time.perf_counter()
        drv = mod.Driver(config, traffic, seed, lambda m: None)
        drv.setup()
        drv.release()
        t_setup = time.perf_counter()
        ref = drv.reference()
        t_ref = time.perf_counter()
        if seed in args.seeds:
            emit({"cell": work["name"], "seed": seed, "kind": "program",
                  "numbers": drv.numbers(ref)})
        if seed in args.control_seeds:
            emit({"cell": work["name"], "seed": seed, "kind": "control",
                  "numbers": drv.numbers(ref, prog=drv.reference(control=True))})
        if seed in args.witness_seeds:
            for name in ("bf16", "bf16q"):
                emit({"cell": work["name"], "seed": seed, "kind": name,
                      "numbers": drv.numbers(ref, prog=drv.reference(precision=name))})
        for fault in mod.FAULTS if seed in args.fault_seeds else ():
            if fault in (None, "unchanged"):
                continue
            bad = mod.Driver(config, traffic, seed, lambda m: None,
                             fault=fault)
            bad.setup()
            bad.release()
            emit({"cell": work["name"], "seed": seed, "kind": fault,
                  "numbers": bad.numbers(ref)})
            del bad
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s, the "
              f"reference {t_ref - t_setup:.1f} s of it", file=sys.stderr,
              flush=True)
        del drv, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
