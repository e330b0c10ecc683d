"""Production training launcher (deploy path).

Runs federated rounds of ``DeployFedLT`` for a selected architecture on
whatever devices exist.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --smoke --rounds 10 --checkpoint-dir ckpts/

``--smoke`` swaps in the reduced config (CPU-runnable); without it the full
config is used and the device must be able to hold it.  :func:`train` is
the same loop as a function, for callers that pick their own config
(``chip_smoke.py`` cuts the depth of a published config).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from .. import obs
from ..checkpoint.store import save
from ..configs import ARCHS, smoke_variant
from ..core.deploy import DeployFedLT, emit_round_series
from ..data.synthetic import make_batch

#: the checkout's persistent compile cache (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep JAX's persistent compile cache at a fixed path; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to :data:`CACHE_DIR` —
    fixed, because the path is part of the cache key and a moving
    directory never hits.  Entry points call this; importing the library
    sets no cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def _agent_batches(cfg, k, agents, batch, seq):
    keys = [jax.random.fold_in(jax.random.PRNGKey(11 + i), k)
            for i in range(agents)]
    per = [make_batch(cfg, kk, batch, seq) for kk in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


def train(cfg, *, rounds: int = 10, agents: int = 2, batch: int = 2,
          seq: int = 128, n_epochs: int = 2, gamma: float = 0.02,
          rho: float = 10.0, compress: bool = True, pack_wire: bool = False,
          checkpoint_dir=None, checkpoint_every: int = 50, trace=None,
          ledger=None) -> dict:
    """Run ``rounds`` federated rounds of ``DeployFedLT`` on ``cfg``.

    The round is compiled once ahead of the loop, with the state donated
    (the new state reuses the old one's buffers).  Returns ``n_params``
    (per model copy), the compiled round's ``memory`` analysis, and the
    per-round ``losses`` and wall ``seconds`` (state ready on the device).
    """
    alg = DeployFedLT(cfg=cfg, n_epochs=n_epochs, gamma=gamma, rho=rho,
                      compress=compress, pack_wire=pack_wire)
    state = alg.init(jax.random.PRNGKey(0), agents)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.y_hat))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M agents={agents}")

    step = jax.jit(alg.round_step, donate_argnums=0).lower(
        state, _agent_batches(cfg, 0, agents, batch, seq)).compile()
    out = {"n_params": n_params, "memory": step.memory_analysis(),
           "losses": [], "seconds": []}

    trace_ctx = (obs.tracing(trace, stream_every=64, scenario=cfg.name,
                             algorithm="DeployFedLT", mode="deploy",
                             n_agents=agents)
                 if trace else contextlib.nullcontext())
    with trace_ctx:
        for k in range(rounds):
            b = _agent_batches(cfg, k, agents, batch, seq)
            t0 = time.perf_counter()
            state, metrics = jax.block_until_ready(step(state, b))
            dt = time.perf_counter() - t0
            emit_round_series(k, metrics)
            loss = float(metrics["loss"])
            out["losses"].append(loss)
            out["seconds"].append(dt)
            print(f"round {k:5d}  loss={loss:.4f}  ({dt:.3f}s)")
            if (checkpoint_dir and ((k + 1) % checkpoint_every == 0
                                    or k == rounds - 1)):
                path = os.path.join(checkpoint_dir, f"round_{k + 1:06d}")
                save(path, state.y_hat, step=k + 1)
                print(f"  checkpoint → {path}.npz")
    if trace and ledger:
        from ..obs.ledger import ingest
        entry, added = ingest(trace, ledger)
        print(f"ledger: {entry['run_id']}"
              + ("" if added else " (already present)"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--agents", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-epochs", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.02)
    ap.add_argument("--rho", type=float, default=10.0)
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="stream a repro.obs trace here (.jsonl / "
                         ".jsonl.gz); tail it live with "
                         "`python -m repro.obs watch PATH`")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="fold the finished trace into this run ledger")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = smoke_variant(cfg)
    train(cfg, rounds=args.rounds, agents=args.agents, batch=args.batch,
          seq=args.seq, n_epochs=args.n_epochs, gamma=args.gamma,
          rho=args.rho, compress=not args.no_compress,
          checkpoint_dir=args.checkpoint_dir,
          checkpoint_every=args.checkpoint_every, trace=args.trace,
          ledger=args.ledger)


if __name__ == "__main__":
    main()
