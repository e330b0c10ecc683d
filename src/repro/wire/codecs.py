"""Wire codecs: compressor output → exact on-wire bytes (paper §2.4).

Every δ-approximate compressor in :mod:`repro.core.compression` maps to a
codec that serializes its *float output* into the bytes a satellite would
actually transmit, and back — losslessly:

=================  ========  =====================================  =============
compressor          codec     wire format                            bits/scalar
=================  ========  =====================================  =============
UniformQuantizer    quant     b-bit level indices bit-packed into    b = ⌈log₂(L+1)⌉
                              uint32 words (b = ⌈log₂(L+1)⌉)
ScaledSign          sign      1 bit/coordinate + one f32 scale       1
TopK / RandD        sparse    k packed ⌈log₂ n⌉-bit indices +        (⌈log₂n⌉+8·itemsize)·k/n
                              k raw values
Identity            dense     raw little-endian floats               8·itemsize
=================  ========  =====================================  =============

Bit-packing runs through the Pallas kernels in
:mod:`repro.kernels.pack_bits` (interpret mode on CPU, compiled on TPU).
Round-trip guarantee: ``codec.decode(codec.encode(C(x))) == C(x)``
bit-exactly, for the matching compressor ``C`` (for ``UniformQuantizer``
this requires ``clip=True`` — an out-of-range lattice point has no index
on the wire, exactly as in :func:`repro.core.compression.quantize_encode`).

``encode`` is host-side serialization (the sparse codec's payload size
depends on the actual nonzero count); use :meth:`WireCodec.tree_nbytes`
for the analytic size under nominal sparsity.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compression import (Compressor, Identity, RandD, ScaledSign,
                                TopK, UniformQuantizer, quantize_decode,
                                quantize_encode, wire_index_bits)
from ..kernels import ops as _ops
from ..kernels.pack_bits import logical_words, pack_bits, unpack_bits
from .message import LeafWire, WireMessage, leaf_header_nbytes


def _interpret(flag: Optional[bool]) -> bool:
    if flag is not None:
        return flag
    return _ops._interpret()


def index_bits(n: int) -> int:
    """Bits needed to address a coordinate in an n-vector."""
    return max(1, math.ceil(math.log2(max(n, 2))))


class WireCodec:
    """Base codec: per-leaf encode/decode + exact byte accounting."""

    kind: str = "?"
    HEADER_EXTRA_NBYTES: int = 0

    # -- per-leaf ---------------------------------------------------------
    def encode_leaf(self, x) -> LeafWire:  # pragma: no cover - abstract
        raise NotImplementedError

    def decode_leaf(self, lw: LeafWire):   # pragma: no cover - abstract
        raise NotImplementedError

    # -- exact accounting -------------------------------------------------
    def leaf_header_nbytes(self, ndim: int) -> int:
        return leaf_header_nbytes(ndim, self.HEADER_EXTRA_NBYTES)

    def leaf_payload_nbytes(self, n: int, itemsize: int = 4) -> int:
        raise NotImplementedError

    def leaf_nbytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        return (self.leaf_header_nbytes(len(shape))
                + self.leaf_payload_nbytes(n, itemsize))

    def wire_bits_per_scalar_measured(self, n: int, itemsize: int = 4
                                      ) -> float:
        """Exact bits/scalar of an n-vector leaf, headers included."""
        return 8.0 * self.leaf_nbytes((n,), itemsize) / n

    # -- pytree -----------------------------------------------------------
    def encode(self, tree) -> WireMessage:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return WireMessage([self.encode_leaf(x) for x in leaves], treedef)

    def decode(self, msg: WireMessage):
        return jax.tree_util.tree_unflatten(
            msg.treedef, [self.decode_leaf(lw) for lw in msg.leaves])

    def tree_nbytes(self, tree) -> int:
        """Analytic on-wire size of ``encode(tree)`` under nominal
        sparsity, message header included."""
        from .message import MESSAGE_HEADER_NBYTES
        leaves = jax.tree_util.tree_leaves(tree)
        return MESSAGE_HEADER_NBYTES + sum(
            self.leaf_nbytes(x.shape, x.dtype.itemsize) for x in leaves)


@dataclasses.dataclass(frozen=True)
class QuantCodec(WireCodec):
    """b-bit packed level indices for :class:`UniformQuantizer`.

    Header extras: levels ``u32`` + vmin ``f32`` + vmax ``f32``.
    """

    levels: int = 255
    vmin: float = -1.0
    vmax: float = 1.0
    interpret: Optional[bool] = None

    kind = "quant"
    HEADER_EXTRA_NBYTES = 12

    @property
    def bits(self) -> int:
        return wire_index_bits(self.levels)

    def encode_leaf(self, x) -> LeafWire:
        idx = quantize_encode(x, self.levels, self.vmin,
                              self.vmax).astype(jnp.uint32)
        words = pack_bits(idx, self.bits, interpret=_interpret(self.interpret))
        return LeafWire(self.kind, tuple(x.shape), x.dtype, {"words": words},
                        self.leaf_header_nbytes(x.ndim),
                        self.leaf_payload_nbytes(x.size),
                        meta={"bits": self.bits})

    def decode_leaf(self, lw: LeafWire):
        n = int(np.prod(lw.shape, dtype=np.int64)) if lw.shape else 1
        idx = unpack_bits(lw.payload["words"], self.bits, n,
                          interpret=_interpret(self.interpret))
        return quantize_decode(idx, self.levels, self.vmin, self.vmax,
                               jnp.float32).astype(lw.dtype).reshape(lw.shape)

    def leaf_payload_nbytes(self, n: int, itemsize: int = 4) -> int:
        return 4 * logical_words(n, self.bits)


@dataclasses.dataclass(frozen=True)
class SignCodec(WireCodec):
    """1-bit sign packing for :class:`ScaledSign` (+ one f32 scale).

    Header extras: scale ``f32``.  Requires the binarized sign convention
    ``sign(0) := +1`` (which :class:`ScaledSign` uses), so every
    coordinate is exactly ±scale and one bit round-trips it.
    """

    interpret: Optional[bool] = None

    kind = "sign"
    HEADER_EXTRA_NBYTES = 4

    def encode_leaf(self, x) -> LeafWire:
        flat = x.reshape(-1)
        scale = jnp.max(jnp.abs(flat)).astype(jnp.float32)
        bit = (flat > 0).astype(jnp.uint32)
        words = pack_bits(bit, 1, interpret=_interpret(self.interpret))
        return LeafWire(self.kind, tuple(x.shape), x.dtype,
                        {"words": words, "scale": scale},
                        self.leaf_header_nbytes(x.ndim),
                        self.leaf_payload_nbytes(x.size),
                        meta={"bits": 1})

    def decode_leaf(self, lw: LeafWire):
        n = int(np.prod(lw.shape, dtype=np.int64)) if lw.shape else 1
        bit = unpack_bits(lw.payload["words"], 1, n,
                          interpret=_interpret(self.interpret))
        s = lw.payload["scale"]
        return jnp.where(bit == 1, s, -s).astype(lw.dtype).reshape(lw.shape)

    def leaf_payload_nbytes(self, n: int, itemsize: int = 4) -> int:
        return 4 * logical_words(n, 1)


@dataclasses.dataclass(frozen=True)
class SparseCodec(WireCodec):
    """Index+value packing for :class:`TopK` / :class:`RandD` outputs.

    Indices are bit-packed at ⌈log₂ n⌉ bits through the Pallas kernel;
    values ride raw in the leaf dtype.  ``encode`` measures the *actual*
    nonzero count (host-side), so the accounted bytes are exactly what a
    transmitter would send — ties in TopK or zero-valued kept coordinates
    in RandD shrink the payload below the nominal ``fraction·n``.

    Header extras: k ``u32``.
    """

    fraction: float = 0.1
    interpret: Optional[bool] = None

    kind = "sparse"
    HEADER_EXTRA_NBYTES = 4

    def encode_leaf(self, x) -> LeafWire:
        flat = x.reshape(-1)
        n = flat.size
        nz = np.nonzero(np.asarray(flat))[0].astype(np.uint32)
        k = int(nz.size)
        bits = index_bits(n)
        words = pack_bits(jnp.asarray(nz), bits,
                          interpret=_interpret(self.interpret))
        vals = flat[jnp.asarray(nz, jnp.int32)]
        payload_nbytes = (4 * logical_words(k, bits)
                          + k * x.dtype.itemsize)
        return LeafWire(self.kind, tuple(x.shape), x.dtype,
                        {"words": words, "values": vals},
                        self.leaf_header_nbytes(x.ndim), payload_nbytes,
                        meta={"bits": bits, "k": k})

    def decode_leaf(self, lw: LeafWire):
        n = int(np.prod(lw.shape, dtype=np.int64)) if lw.shape else 1
        k = lw.meta["k"]
        idx = unpack_bits(lw.payload["words"], lw.meta["bits"], k,
                          interpret=_interpret(self.interpret))
        out = jnp.zeros((n,), lw.dtype)
        out = out.at[idx.astype(jnp.int32)].set(lw.payload["values"])
        return out.reshape(lw.shape)

    def leaf_payload_nbytes(self, n: int, itemsize: int = 4) -> int:
        k = max(1, int(round(self.fraction * n)))
        return 4 * logical_words(k, index_bits(n)) + k * itemsize


@dataclasses.dataclass(frozen=True)
class DenseCodec(WireCodec):
    """Raw float serialization for :class:`Identity` (no compression)."""

    kind = "dense"
    HEADER_EXTRA_NBYTES = 0

    def encode_leaf(self, x) -> LeafWire:
        return LeafWire(self.kind, tuple(x.shape), x.dtype,
                        {"raw": x.reshape(-1)},
                        self.leaf_header_nbytes(x.ndim),
                        self.leaf_payload_nbytes(x.size, x.dtype.itemsize))

    def decode_leaf(self, lw: LeafWire):
        return lw.payload["raw"].reshape(lw.shape)

    def leaf_payload_nbytes(self, n: int, itemsize: int = 4) -> int:
        return n * itemsize


def codec_for(compressor: Compressor, *,
              interpret: Optional[bool] = None) -> Optional[WireCodec]:
    """The wire codec matching a compressor (None if it has no codec)."""
    if isinstance(compressor, UniformQuantizer):
        return QuantCodec(compressor.levels, compressor.vmin,
                          compressor.vmax, interpret=interpret)
    if isinstance(compressor, ScaledSign):
        return SignCodec(interpret=interpret)
    if isinstance(compressor, (TopK, RandD)):
        return SparseCodec(compressor.fraction, interpret=interpret)
    if isinstance(compressor, Identity):
        return DenseCodec()
    return None


def measure_tree_bytes(compressor: Compressor, tree, *,
                       interpret: Optional[bool] = None) -> float:
    """Measured on-wire bytes of one message: really encode ``tree``
    through the compressor's codec and count.  Falls back to the nominal
    ``wire_bits_per_scalar`` estimate for compressors without a codec."""
    codec = codec_for(compressor, interpret=interpret)
    if codec is None:
        n = sum(x.size for x in jax.tree_util.tree_leaves(tree))
        return n * compressor.wire_bits_per_scalar() / 8.0
    return float(codec.encode(tree).nbytes)
