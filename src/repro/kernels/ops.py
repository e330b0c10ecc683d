"""jit'd public wrappers around the Pallas kernels.

``use_pallas`` selects the kernel path; interpret mode is chosen
automatically (CPU → interpret=True for validation, TPU → compiled kernel).

Every wrapper is wrapped in a dispatch hook (:func:`_traced`): with an
active :mod:`repro.obs` tracer each call runs under a
``jax.profiler.TraceAnnotation`` named ``repro.kernels.<name>`` (so the
dispatch shows up named inside ``jax.profiler`` captures), records a
host-side ``kernel`` record and feeds the ``kernel.<name>`` phase of
:mod:`repro.obs.prof` — all dispatch time: device compute is async, and
a capture's device plane holds it (the kernel's custom call carries its
name).  Inside ``jit`` the hook runs once, at trace time.  Disabled, the
hook is one module attribute read and a ``None`` check, and no span is
opened.
"""
from __future__ import annotations

import functools
import time

import jax

from ..obs.trace import active as _obs_active
from . import ref
from .compress_pipeline import quant_pipeline as _quant_pipeline
from .compress_pipeline import sign_pipeline as _sign_pipeline
from .erasure_mask import erasure_mask as _erasure_mask
from .flash_attention import flash_attention as _flash
from .pack_bits import pack_bits as _pack_bits
from .pack_bits import unpack_bits as _unpack_bits
from .quantize_ef import quantize_ef as _quant_ef


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _traced(fn):
    """Kernel-dispatch trace hook (zero-cost with no active tracer)."""
    name = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        trc = _obs_active()
        if trc is None:
            return fn(*args, **kwargs)
        with jax.profiler.TraceAnnotation(f"repro.kernels.{name}"):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dur = time.perf_counter() - t0
        # same record Tracer.span would emit, but timed manually so the
        # duration can also feed the phase profiler (kernel.<name>)
        trc.raw({"kind": "kernel", "name": name,
                 "t_host": t0 - trc._t0_host, "dur_host": dur})
        trc.prof.add("kernel." + name, dur)
        trc.metrics.counter("kernel_dispatches").add(1.0, name=name)
        return out

    return wrapper


@_traced
def pack_bits(x, bits: int, *, use_pallas: bool = True):
    """Pack b-bit values into uint32 wire words (repro.wire layout)."""
    if not use_pallas:
        return ref.pack_bits_ref(x, bits)
    return _pack_bits(x, bits, interpret=_interpret())


@_traced
def unpack_bits(words, bits: int, n: int, *, use_pallas: bool = True):
    """Inverse of :func:`pack_bits`: first ``n`` values, flat uint32."""
    if not use_pallas:
        return ref.unpack_bits_ref(words, bits, n)
    return _unpack_bits(words, bits, n, interpret=_interpret())


@_traced
def quantize_ef(msg, cache, *, levels=255, vmin=-0.25, vmax=0.25,
                use_pallas: bool = True):
    if not use_pallas:
        return ref.quantize_ef_ref(msg, cache, levels=levels, vmin=vmin,
                                   vmax=vmax)
    return _quant_ef(msg, cache, levels=levels, vmin=vmin, vmax=vmax,
                     interpret=_interpret())


@_traced
def quant_pipeline(msg, cache, *, levels=255, vmin=-1.0, vmax=1.0,
                   use_pallas: bool = True):
    """Fused quantize→EF→pack sweep: (msg, cache) → (wire words, new cache).

    One kernel dispatch replacing the separate quantize_ef → pack_bits
    chain; output words are bit-identical to the unfused path.
    """
    if not use_pallas:
        return ref.quant_pipeline_ref(msg, cache, levels=levels, vmin=vmin,
                                      vmax=vmax)
    return _quant_pipeline(msg, cache, levels=levels, vmin=vmin, vmax=vmax,
                           interpret=_interpret())


@_traced
def sign_pipeline(msg, cache, *, use_pallas: bool = True):
    """Fused scaled-sign→EF→1-bit-pack sweep → (words, scale, new cache)."""
    if not use_pallas:
        return ref.sign_pipeline_ref(msg, cache)
    return _sign_pipeline(msg, cache, interpret=_interpret())


@_traced
def erasure_mask(words, *, p: float, seed: int = 0, segment_words: int = 32,
                 use_pallas: bool = True):
    """Counter-based segment erasure over packed wire words → (masked,
    keep mask).  Lossy transport of the fused uplink, on-device."""
    if not use_pallas:
        return ref.erasure_mask_ref(words, p=p, seed=seed,
                                    segment_words=segment_words)
    return _erasure_mask(words, p=p, seed=seed, segment_words=segment_words,
                         interpret=_interpret())


@_traced
def attention(q, k, v, *, causal=True, window=None, softcap=None,
              use_pallas: bool = True, block_q: int = 128, block_k: int = 128):
    """(B,S,H,D) attention; kv heads must be pre-expanded to match q."""
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       softcap=softcap)
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=_interpret())
