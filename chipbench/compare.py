"""The comparison that decides ``correct`` for a training cell.

Each number is a gap of norms taken by the worst leaf: for every leaf,
``|‖program‖ − ‖reference‖|`` over the larger of the reference's norm of
that leaf and the median moved leaf's, and the largest of these.  Leaves whose
reference change after the first round is under a thousandth of the
median leaf's are left out of the change numbers: they move by round-off
alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: a leaf counts in the change numbers when its reference change after the
#: first round is at least this share of the median leaf's
KEEP_SHARE = 1e-3


def _norm(x, base=None):
    x = x.astype(jnp.float32)
    if base is not None:
        x = x - base.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


@jax.jit
def device_norms(tree, base=None):
    """Norm of each leaf (of ``leaf − base`` where a base tree is given),
    in float32, as a flat list in tree order."""
    leaves = jax.tree_util.tree_leaves(tree)
    bases = (jax.tree_util.tree_leaves(base) if base is not None
             else [None] * len(leaves))
    return [_norm(x, b) for x, b in zip(leaves, bases)]


@jax.jit
def device_norms_stacked(tree, base=None):
    """Per-agent norms of each agent-stacked leaf: a list of ``(A,)``."""
    leaves = jax.tree_util.tree_leaves(tree)
    bases = (jax.tree_util.tree_leaves(base) if base is not None
             else [None] * len(leaves))
    return [jax.vmap(lambda x, b=b: _norm(x, b))(x)
            for x, b in zip(leaves, bases)]


def worst_gap(prog, ref, keep=None) -> float:
    """Largest ``|prog − ref| / max(ref, median(ref))`` over the leaves
    (flat arrays of norms, ``keep`` a boolean mask of the leaves that
    count)."""
    prog = np.asarray(prog, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    if keep is None:
        keep = np.ones(ref.shape, bool)
    keep = np.asarray(keep, bool).ravel()
    if not keep.any():
        raise ValueError("no leaf left to compare")
    moved = ref[keep][ref[keep] > 0]
    if not moved.size:
        return 0.0 if not np.any(prog[keep]) else float("inf")
    den = np.maximum(ref[keep], float(np.median(moved)))
    return float(np.max(np.abs(prog[keep] - ref[keep]) / den))


def kept_leaves(ref_change) -> np.ndarray:
    """Leaves whose reference change is at least ``KEEP_SHARE`` of the
    median moved leaf's (leaves that did not move at all, such as agents
    that took no part, are out)."""
    ref_change = np.asarray(ref_change, np.float64).ravel()
    moved = ref_change[ref_change > 0]
    if not moved.size:
        return np.zeros(ref_change.shape, bool)
    return ref_change >= KEEP_SHARE * float(np.median(moved))


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, lines)``: each number beside its limit.  A number
    missing, not finite or above its limit is not correct."""
    lines = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and np.isfinite(value) and value <= limit)
        ok = ok and good
        lines[name] = {"value": None if value is None else float(value),
                       "limit": float(limit)}
    return ok, lines
