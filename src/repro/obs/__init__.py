"""Observability: structured tracing + metrics for the constellation sim
and the federated stack.

The paper's claims are about *communication* — fewer uplinks, smaller
wires, error feedback recovering compression loss — so this subsystem
makes the communication visible: where bytes, retries, and staleness
accumulate inside a round, per link and per contact window, instead of
end-of-run aggregates only.

Three layers:

* :mod:`repro.obs.trace` — a near-zero-overhead-when-disabled
  :class:`Tracer` of typed event records (round, delivery, ARQ
  retransmission, cohort, EF revert, kernel dispatch, link-budget
  sample), emitted by the instrumented engine (both the heapq oracle and
  the fast batch engine — same schema), ``SpaceRunner``, the channel
  stack, and :mod:`repro.kernels.ops`; and :class:`span`, the one host
  span, which always names its stage in ``jax.profiler`` captures (on
  the device trace's timeline) and records it only under a Tracer.  The
  spans: ``runner.round`` (``round=k``), ``runner.schedule``,
  ``runner.dispatch`` and ``runner.revert`` in ``SpaceRunner``;
  ``engine.plan_extend``, ``engine.assign``, ``engine.state_build`` and
  ``engine.event_loop`` in the fast sync engine;
* :mod:`repro.obs.metrics` — counters/histograms (bytes per link,
  retransmitted bytes, delivery latency, staleness, lost fraction)
  snapshotted into every trace;
* :mod:`repro.obs.summary` / :mod:`repro.obs.chrome` — summarize
  (human table or ``--json`` machine form), diff (localize the first
  fast-vs-oracle divergence), check invariants (bytes conservation —
  the CI smoke), and export Chrome/Perfetto traces;
* :mod:`repro.obs.ledger` / :mod:`repro.obs.report` — cross-run
  experiment tracking: every run's per-round ``series`` curves (e_K,
  bytes_up/down/air, EF-residual norm, staleness, lost fraction) fold
  into an append-only run ledger keyed by content-hash run ids; the
  report renders cross-run tables and the bytes-to-ground vs e_K
  frontier, ``watch`` tails a live trace (reader-side only), and
  ``convgate`` gates fresh convergence curves against the committed
  ``CONV_reference.json`` in CI;
* :mod:`repro.obs.prof` — the phase-attribution profiler: both engines
  bracket their real stages (plan extension, assignment, window fits,
  channel commits, batched routing, kernel dispatches) so ``prof``
  renders per-phase self/total/p50/p99 with an explicit unattributed
  residual, ``perfdiff`` names the phases behind a perf regression, and
  ``bench-history`` tracks ``BENCH_*.json`` emissions over time with
  regression-onset localization.

Quickstart::

    from repro import obs
    with obs.tracing("run.jsonl", scenario="mega-1000"):
        runner.run(alg, state, data, n_rounds=50, key=key)
    # then:  python -m repro.obs summarize run.jsonl [--json]
    #        python -m repro.obs ingest run.jsonl --ledger runs/ledger.jsonl
    #        python -m repro.obs report --ledger runs/ledger.jsonl
    #        python -m repro.obs watch run.jsonl --total 50   # live runs
    #        python -m repro.obs convgate                     # CI gate
    #        python -m repro.obs diff fast.jsonl oracle.jsonl
    #        python -m repro.obs check run.jsonl
    #        python -m repro.obs chrome run.jsonl -o run.perfetto.json
    #        python -m repro.obs prof run.jsonl --flame run.folded
    #        python -m repro.obs perfdiff old.jsonl new.jsonl
    #        python -m repro.obs bench-history bench_out/BENCH_sim.json

Paths ending in ``.gz`` read and write gzip-compressed; long runs can
stream with bounded memory (``obs.tracing(path, stream_every=N)``).

Disabled (the default) the cost anywhere in the stack is a module
attribute read per round / per kernel dispatch, plus one
``TraceAnnotation`` object per :class:`span` (about a microsecond of
host time each, some seven a sync round) — enforced by the gated
``sim.trace_overhead`` benchmark (<5% enabled, parity disabled).  A
``jax.profiler`` capture of a run shows the spans without a Tracer::

    jax.profiler.start_trace("prof_dir")
    exp.run(state, data, 10, key)
    jax.profiler.stop_trace()     # runner.round ⊃ runner.schedule ⊃ engine.*
"""
from .chrome import chrome_trace, write_chrome_trace
from .ledger import ingest, load_ledger
from .metrics import Counter, Histogram, Metrics
from .prof import (PhaseAcc, attribution, collect, folded, ingest_bench,
                   perfdiff, render_history, render_perfdiff,
                   render_profile)
from .report import convgate, render_frontier, render_report, watch
from .summary import (check, diff, extract_series, render_rounds,
                      summarize, summarize_dict)
from .trace import (Tracer, active, disable, enable, load, span, tracing)

__all__ = [
    "Tracer", "span", "active", "enable", "disable", "tracing", "load",
    "Metrics", "Counter", "Histogram",
    "summarize", "summarize_dict", "extract_series", "render_rounds",
    "diff", "check",
    "ingest", "load_ledger", "render_report", "render_frontier",
    "watch", "convgate",
    "chrome_trace", "write_chrome_trace",
    "PhaseAcc", "collect", "render_profile", "folded", "attribution",
    "perfdiff", "render_perfdiff", "ingest_bench", "render_history",
]
