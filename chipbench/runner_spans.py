"""The program's own host spans of a sync round (``repro.obs.span``), as a
traced run of ``Experiment.run`` leaves them on the window's thread.

``runner.round`` spans one round of ``SpaceRunner._run_sync``; inside it
``runner.schedule`` spans the engine's call and ``runner.dispatch`` the
round's device inputs and the call of the jitted round.  Every span is
clipped to the window, and a child to the round that holds it, so that
the schedule, the dispatch, the rest of the rounds (their self time) and
the window outside every round add up to the window.  A trace with no
``runner.round`` span (a program without these spans) gives None.
"""
from __future__ import annotations

import bisect

ROUND = "runner.round"


def _rounds(trace) -> list:
    """The ``(start, end)`` of each round span, clipped to the window,
    in order of start."""
    a, b = trace.window
    return sorted((max(sp.start, a), min(sp.end, b)) for sp in trace.spans
                  if sp.name == ROUND and sp.end > a and sp.start < b)


def child_s(trace, name: str) -> float:
    """Seconds of the spans ``name`` inside the rounds and the window."""
    rounds = _rounds(trace)
    starts = [s for s, _ in rounds]
    a = trace.window[0]
    total = 0.0
    for sp in trace.spans:
        if sp.name != name:
            continue
        i = bisect.bisect_right(starts, max(sp.start, a)) - 1
        if i < 0:
            continue
        s, e = rounds[i]
        total += max(0.0, min(sp.end, e) - max(sp.start, s))
    return total


def round_s(trace) -> float:
    """Seconds of the window inside a round span."""
    total, last = 0.0, float("-inf")
    for s, e in _rounds(trace):
        s = max(s, last)
        if e > s:
            total += e - s
            last = e
    return total


def per_round_ms(ctx, seconds):
    """``seconds(trace)`` in milliseconds a round of the window, or None
    without a traced round span."""
    trace = ctx.trace
    if not trace or not any(sp.name == ROUND for sp in trace.spans):
        return None
    return 1e3 * seconds(trace) / ctx.rounds
