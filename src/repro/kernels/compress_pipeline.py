"""Fused compress → error-feedback → bit-pack Pallas pipeline kernel.

The per-round uplink of the paper's Algorithm 2 is a three-stage chain:

    corrected = msg + cache            (error feedback, §2.2)
    wire      = C(corrected)           (compression, §2.4)
    words     = pack(wire)             (on-wire serialization, repro.wire)
    new_cache = corrected − wire

Run separately (``quantize_ef`` then ``pack_bits``, or the jnp
``quantize_encode`` chain in ``core.deploy``) every parameter makes two
round trips through HBM: the intermediate integer tensor is written by the
quantizer and re-read by the packer.  This kernel chains all three stages
inside one VMEM tile sweep: read msg + cache → write packed words + new
cache.  The intermediate indices never leave VMEM, so the op hits its
memory floor (2 reads + ~1.03 writes per element for 8-bit wire vs
2 reads + 2 writes unfused — and one kernel dispatch instead of two).

Tiling matches :mod:`repro.kernels.pack_bits` exactly — values in
``(GROUP·R, LANES)`` tiles, words in ``(bits·R, LANES)`` tiles with the
transposed bit-plane layout (bit j of value i at bit position i of word j)
— so fused output words are bit-identical to
``pack_bits(quantize_encode(msg + cache))`` and both ends of the wire
interoperate freely with the unfused path.

Modes
-----
``quant_pipeline``
    b-bit uniform quantization (paper Definition 2, clip=True): the wire
    is ``ceil(log2(levels+1))``-bit level indices.
``sign_pipeline``
    1-bit scaled sign (ScaledSign, sign(0) := +1): the wire is one bit
    per coordinate plus one f32 scale = mean |corrected|.  The scale is a
    global reduction, computed as a read-only jnp pass before the kernel
    (no extra HBM writes); masking, EF update, and packing still fuse.

Top-k / rand-d sparsification is NOT fused: selecting the k-th largest
magnitude of ``msg + cache`` is a cross-tile reduction over the corrected
signal, and compacting survivors into the sparse index+value wire format
is a gather — neither fits a single elementwise tile sweep.  Those codecs
keep the :class:`repro.wire.codecs.SparseCodec` path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pack_bits import GROUP, LANES, R, _TILE_VALS, _check_bits
from .quantize_ef import inv_delta

__all__ = ["quant_pipeline", "sign_pipeline", "pipeline_tile_values"]

#: values per kernel tile (same tile as pack_bits: (32·R, 128) = 32768)
pipeline_tile_values = _TILE_VALS


def _pack_planes(v, words_ref, bits):
    """Write uint32 values ``v`` (GROUP·R, LANES) as transposed bit planes."""
    for j in range(bits):
        w = jnp.zeros((R, LANES), jnp.uint32)
        for i in range(GROUP):
            w = w | (((v[i * R:(i + 1) * R, :] >> j) & 1) << i)
        words_ref[j * R:(j + 1) * R, :] = w


def _quant_kernel(msg_ref, cache_ref, words_ref, newc_ref, *,
                  bits, levels, vmin, vmax):
    msg = msg_ref[...].astype(jnp.float32)
    cache = cache_ref[...].astype(jnp.float32)
    delta = (vmax - vmin) / levels
    corrected = msg + cache
    idx = jnp.floor((jnp.clip(corrected, vmin, vmax) - vmin) * inv_delta(delta)
                    + 0.5)
    idx = jnp.clip(idx, 0.0, float(levels))
    decoded = idx * delta + vmin
    newc_ref[...] = (corrected - decoded).astype(newc_ref.dtype)
    # Mosaic has no f32 -> uint32 cast; the index is in [0, levels]
    _pack_planes(idx.astype(jnp.int32).astype(jnp.uint32), words_ref, bits)


def _sign_kernel(msg_ref, cache_ref, scale_ref, words_ref, newc_ref):
    msg = msg_ref[...].astype(jnp.float32)
    cache = cache_ref[...].astype(jnp.float32)
    scale = scale_ref[0, 0]
    corrected = msg + cache
    bit = (corrected >= 0.0)
    decoded = jnp.where(bit, scale, -scale)
    newc_ref[...] = (corrected - decoded).astype(newc_ref.dtype)
    _pack_planes(bit.astype(jnp.uint32), words_ref, 1)


def _tile(x, fill=0.0):
    """Flatten + pad to whole (GROUP·R, LANES) tiles; returns
    (2-D array, n, tiles).

    ``fill`` is the pad value for the tail.  The quant path pads ``msg``
    with ``vmin`` (and ``cache`` with 0) so padded slots quantize to index
    0 and the packed words match the unfused ``pack_bits`` zero-padding
    bit-for-bit; the sign path pads with −1 for the same reason (bit 0).
    """
    n = x.size
    flat = x.reshape(-1)
    tiles = max(1, -(-n // _TILE_VALS))
    pad = tiles * _TILE_VALS - n
    if pad:
        flat = jnp.pad(flat, (0, pad), constant_values=fill)
    return flat.reshape(tiles * GROUP * R, LANES), n, tiles


@functools.partial(jax.jit, static_argnames=("levels", "vmin", "vmax",
                                             "interpret"))
def quant_pipeline(msg, cache, *, levels: int = 255, vmin: float = -1.0,
                   vmax: float = 1.0, interpret: bool = True):
    """Fused quantize + EF + pack: (msg, cache) → (wire words, new cache).

    ``words`` is a flat uint32 array of ``tiles·bits·R·LANES`` packed
    words, bit-identical to
    ``pack_bits(quantize_encode(msg + cache, levels, vmin, vmax), bits)``
    with ``bits = wire_index_bits(levels)``; ``new_cache`` has the shape
    and dtype of ``msg`` and equals ``(msg + cache) − decode(words)``.
    interpret=True runs the kernel body in Python on CPU (validation),
    interpret=False targets the TPU backend.
    """
    from ..core.compression import wire_index_bits  # lazy: core imports us
    bits = wire_index_bits(levels)
    _check_bits(bits)
    shape, dtype = msg.shape, msg.dtype
    m2, n, tiles = _tile(msg, fill=vmin)   # pad quantizes to index 0
    c2, _, _ = _tile(cache)
    words, newc = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits, levels=levels,
                          vmin=vmin, vmax=vmax),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bits * R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * bits * R, LANES), jnp.uint32),
            jax.ShapeDtypeStruct(m2.shape, dtype),
        ],
        interpret=interpret,
    )(m2, c2)
    return words.reshape(-1), newc.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_pipeline(msg, cache, *, interpret: bool = True):
    """Fused scaled-sign + EF + 1-bit pack: → (words, scale, new cache).

    ``scale = mean |msg + cache|`` (one read-only reduction pass);
    ``words`` packs ``corrected >= 0`` bits in the repro.wire layout and
    ``new_cache = corrected − (±scale)``.
    """
    shape, dtype = msg.shape, msg.dtype
    m2, n, tiles = _tile(msg, fill=-1.0)   # pad signs negative → bit 0
    c2, _, _ = _tile(cache)
    corrected_flat = (msg.reshape(-1).astype(jnp.float32)
                      + cache.reshape(-1).astype(jnp.float32))
    scale = jnp.mean(jnp.abs(corrected_flat)).astype(jnp.float32)
    words, newc = pl.pallas_call(
        _sign_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1 * R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((GROUP * R, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * 1 * R, LANES), jnp.uint32),
            jax.ShapeDtypeStruct(m2.shape, dtype),
        ],
        interpret=interpret,
    )(m2, c2, scale.reshape(1, 1))
    return words.reshape(-1), scale, newc.reshape(-1)[:n].reshape(shape)
