#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the fleet-sharded round

Phase A, the paper's path: ``repro.api.Experiment`` on ``walker-kiruna``,
sync, Fed-LT with a 10-level quantizer and error feedback on both links and
the fused compress→EF→pack uplink.  Checks that every ``e_K`` is finite,
that the round's program holds the Pallas kernel (``tpu_custom_call``),
and that the kernel's words and EF cache on a 2048×5632 leaf equal the
jnp oracle bit for bit.

Phase B, a language-model payload at published width: StableLM-2-1.6B
(d_model 2048, vocab 100352) with its depth cut to fit one chip, trained
through ``repro.launch.train.train`` for 3 rounds with 2 agents and the
packed wire.  Checks finite losses and the memory the round takes.

``--four-chips`` runs only ``FedLT.run`` with the agent axis sharded over a
four-device fleet mesh against the same run unsharded, and compares them.

Exits non-zero with the reason when JAX finds no TPU, when a phase raises
or when a check fails; there is no CPU fallback.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

#: one StableLM-2-1.6B MLP weight (d_model × d_ff)
LEAF = (2048, 5632)
#: StableLM-2-1.6B layers kept: the largest depth whose round compiles to
#: at most MAX_ROUND_BYTES on a v5e (3 layers take 11.49 GB, 4 take 13.08)
LM_LAYERS = 3
MAX_ROUND_BYTES = 12e9
HBM_BYTES = 16e9


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _logistic_fedlt(n_agents: int, dim: int):
    """The paper's example setting: logistic data and Fed-LT with a
    10-level quantizer, EF on both links and the fused uplink."""
    from repro.core.compression import UniformQuantizer
    from repro.core.error_feedback import EFChannel
    from repro.core.fedlt import FedLT
    from repro.data.logistic import generate, make_local_loss

    data, _ = generate(jax.random.PRNGKey(0), n_agents=n_agents, m=200,
                       dim=dim)
    quant = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    alg = FedLT(loss=make_local_loss(eps=50.0, n_agents=n_agents),
                n_epochs=10, gamma=0.005, rho=20.0, uplink=EFChannel(quant),
                downlink=EFChannel(quant), fused_uplink=True)
    return alg, quant, data


def phase_a(rounds: int = 5) -> None:
    from repro.api import Experiment
    from repro.core.fedlt import optimality_error
    from repro.data.logistic import solve_global
    from repro.kernels import ops, ref

    n_agents, dim = 100, 100
    alg, quant, data = _logistic_fedlt(n_agents, dim)
    x_star = solve_global(data, eps=50.0)
    exp = Experiment.from_scenario("walker-kiruna", algorithm=alg,
                                   compressor=quant, measure="cohort")
    state = exp.init(jnp.zeros((dim,)), n_agents)

    # the round Experiment.run jits, lowered for the same arguments
    hlo = jax.jit(alg.round).lower(state, data, jnp.ones((n_agents,), bool),
                                   jax.random.PRNGKey(0)).as_text()
    kernel = "tpu_custom_call" in hlo
    print(f"phase A: fused uplink kernel in the round: {kernel}")
    check(kernel, "the round has no tpu_custom_call: the fused uplink fell "
                  "back to the vmapped path")

    res = exp.run(state, data, rounds, jax.random.PRNGKey(2),
                  error_fn=lambda s: optimality_error(s.x, x_star),
                  log_every=1)
    errs = [lg.error for lg in res.logs]
    print(f"phase A: walker-kiruna sync, {rounds} rounds, "
          f"n_active={[lg.n_active for lg in res.logs]}")
    print(f"phase A: e_K={errs}")
    check(len(errs) == rounds
          and all(e is not None and math.isfinite(e) for e in errs),
          f"e_K not finite in every round: {errs}")

    for levels in (10, 255):
        k1, k2 = jax.random.split(jax.random.PRNGKey(levels))
        msg = jax.random.normal(k1, LEAF, jnp.float32) * 0.5
        cache = jax.random.normal(k2, LEAF, jnp.float32) * 0.01
        words, newc = ops.quant_pipeline(msg, cache, levels=levels,
                                         vmin=-1.0, vmax=1.0)
        words_ref, newc_ref = jax.jit(functools.partial(
            ref.quant_pipeline_ref, levels=levels, vmin=-1.0,
            vmax=1.0))(msg, cache)
        bad_w = int(np.sum(np.asarray(words) != np.asarray(words_ref)))
        bad_c = int(np.sum(np.asarray(newc).view(np.uint32)
                           != np.asarray(newc_ref).view(np.uint32)))
        print(f"phase A: quant_pipeline levels={levels} on {LEAF}: "
              f"{bad_w}/{words.size} words and {bad_c}/{newc.size} cache "
              f"values differ from the oracle")
        check(bad_w == 0 and bad_c == 0,
              f"quant_pipeline (levels={levels}) is not bit-identical to "
              f"ref.quant_pipeline_ref")


def phase_b(rounds: int = 3) -> None:
    from repro.configs import ARCHS
    from repro.launch.train import train

    full = ARCHS["stablelm-1.6b"]
    cfg = dataclasses.replace(full, n_layers=LM_LAYERS,
                              scan_repeats=LM_LAYERS)
    print(f"phase B: {cfg.name} d_model={cfg.d_model} n_heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    print(f"phase B: reduced n_layers {full.n_layers} -> {cfg.n_layers}")
    out = train(cfg, rounds=rounds, agents=2, batch=2, seq=1024,
                pack_wire=True)
    mem = out["memory"]
    round_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"phase B: params per copy {out['n_params']}")
    print(f"phase B: loss per round {out['losses']}")
    print(f"phase B: wall seconds per round after warm-up "
          f"{out['seconds'][1:]} (smoke timing, not a benchmark)")
    print(f"phase B: compiled round {round_bytes} bytes "
          f"(arguments {mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes})")
    print(f"phase B: peak_bytes_in_use {peak}")
    check(all(math.isfinite(x) for x in out["losses"]),
          f"loss not finite: {out['losses']}")
    check(round_bytes <= MAX_ROUND_BYTES,
          f"round takes {round_bytes} bytes > {MAX_ROUND_BYTES}")
    check(peak < HBM_BYTES, f"peak {peak} bytes >= {HBM_BYTES}")


def four_chips(rounds: int = 5) -> None:
    from repro.launch.sharding import fleet_mesh, shard_fleet

    mesh = fleet_mesh()
    check(mesh is not None and mesh.devices.size == 4,
          f"fleet_mesh() does not span 4 devices: {mesh}")
    # dim != n_agents: shard_fleet tells agent-stacked leaves by their
    # leading dim, and c_down (dim,) must stay replicated
    n_agents, dim = 100, 64
    alg, _, data = _logistic_fedlt(n_agents, dim)
    state = alg.init(jnp.zeros((dim,)), n_agents)
    key = jax.random.PRNGKey(3)

    def run(m):
        return jax.jit(lambda s, d, k: alg.run(s, d, rounds, k,
                                               participation=0.5, mesh=m))

    sharded = run(mesh)
    args = (shard_fleet(state, mesh, n_agents=n_agents),
            shard_fleet(data, mesh, n_agents=n_agents), key)
    kernel = "tpu_custom_call" in sharded.lower(*args).as_text()
    s_sh, info_sh = sharded(*args)
    s_one, info_one = run(None)(state, data, key)
    x_sh, x_one = np.asarray(s_sh.x), np.asarray(s_one.x)
    n_sh, n_one = np.asarray(info_sh["n_active"]), np.asarray(
        info_one["n_active"])
    print(f"four chips: fleet mesh {dict(mesh.shape)} over "
          f"{[d.id for d in mesh.devices.flat]}, {n_agents} agents, "
          f"{rounds} rounds, fused uplink kernel in the sharded round: "
          f"{kernel}")
    print(f"four chips: x shard {s_sh.x.sharding}")
    print(f"four chips: n_active sharded {n_sh.tolist()} "
          f"unsharded {n_one.tolist()}")
    print(f"four chips: max |x_sharded - x_unsharded| "
          f"{float(np.max(np.abs(x_sh - x_one)))}, max |x| "
          f"{float(np.max(np.abs(x_one)))}")
    check(kernel, "the sharded round has no tpu_custom_call")
    check(np.array_equal(n_sh, n_one), "n_active differs")
    check(np.allclose(x_sh, x_one, rtol=1e-5, atol=1e-5),
          "final x of the sharded run differs from the unsharded run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet-sharded round on 4 chips")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: JAX found no TPU (platform {dev.platform!r}); this "
              f"smoke runs on the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.train import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    try:
        if args.four_chips:
            four_chips()
        else:
            phase_a()
            phase_b()
    except CheckFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
