"""Fused quantize + error-feedback Pallas TPU kernel.

The per-round uplink (paper Alg. 2 lines 15–16) touches every parameter
three times when written naively: read (z+c), write the wire ints, write the
new cache.  Fusing them into one VMEM pass makes the op strictly
memory-bound at its floor: read msg + read cache → write wire + write cache
in a single tile sweep (2 reads + 2 writes, no intermediate HBM traffic).

TPU adaptation: tiles are (BLOCK_M, 128)-shaped to match the VPU lane width;
the quantization is pure element-wise VPU work (no MXU), so the kernel's
roofline is the HBM bandwidth — exactly what the fusion minimizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK_M = 256
BLOCK_N = 128


def inv_delta(delta: float) -> float:
    """``1/delta`` rounded as XLA rounds it.

    XLA rewrites ``x / c`` for a constant ``c`` into ``x * (1/c)`` with the
    reciprocal taken in f32, so the jnp oracles in :mod:`.ref` multiply.
    Mosaic keeps a true division, which can land one ulp away and flip a
    level index at a rounding tie; multiplying by this same constant keeps
    the kernels bit-identical to the oracles on the chip as on the CPU.
    """
    return float(np.float32(1.0) / np.float32(delta))


def _kernel(msg_ref, cache_ref, wire_ref, newc_ref, *, levels, vmin, vmax):
    msg = msg_ref[...].astype(jnp.float32)
    cache = cache_ref[...].astype(jnp.float32)
    delta = (vmax - vmin) / levels
    corrected = msg + cache
    idx = jnp.floor((jnp.clip(corrected, vmin, vmax) - vmin) * inv_delta(delta)
                    + 0.5)
    idx = jnp.clip(idx, 0.0, float(levels))
    decoded = idx * delta + vmin
    # Mosaic has no f32 -> uint8/uint16 cast; the index is in [0, levels]
    wire_ref[...] = idx.astype(jnp.int32).astype(wire_ref.dtype)
    newc_ref[...] = (corrected - decoded).astype(newc_ref.dtype)


@functools.partial(jax.jit, static_argnames=("levels", "vmin", "vmax",
                                             "interpret"))
def quantize_ef(msg, cache, *, levels: int = 255, vmin: float = -0.25,
                vmax: float = 0.25, interpret: bool = True):
    """msg/cache: same-shape float arrays → (wire uint8/16, new_cache).

    Arbitrary shapes are flattened and padded to the (BLOCK_M, BLOCK_N) tile
    grid; interpret=True runs the kernel body in Python on CPU (validation),
    interpret=False targets the TPU backend.
    """
    shape, dtype = msg.shape, msg.dtype
    n = msg.size
    flat_m = msg.reshape(-1)
    flat_c = cache.reshape(-1)
    tile = BLOCK_M * BLOCK_N
    pad = (-n) % tile
    if pad:
        flat_m = jnp.pad(flat_m, (0, pad))
        flat_c = jnp.pad(flat_c, (0, pad))
    rows = flat_m.size // BLOCK_N
    m2 = flat_m.reshape(rows, BLOCK_N)
    c2 = flat_c.reshape(rows, BLOCK_N)
    wire_dtype = jnp.uint8 if levels <= 255 else jnp.uint16

    grid = (rows // BLOCK_M,)
    out = pl.pallas_call(
        functools.partial(_kernel, levels=levels, vmin=vmin, vmax=vmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(m2.shape, wire_dtype),
            jax.ShapeDtypeStruct(m2.shape, dtype),
        ],
        interpret=interpret,
    )(m2, c2)
    wire, newc = out
    wire = wire.reshape(-1)[:n].reshape(shape)
    newc = newc.reshape(-1)[:n].reshape(shape)
    return wire, newc
