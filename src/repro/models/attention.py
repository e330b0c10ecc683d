"""Attention: GQA/MQA/MHA with RoPE/M-RoPE, sliding window, KV cache.

Two backends:
  * ``xla``     — plain einsum attention (small shapes, smoke tests, oracle)
  * ``chunked`` — flash-style streaming over KV chunks with running
                  max/denominator (``lax.scan``), never materializing the
                  (S × S) score matrix.  Used by the big dry-run shapes; for
                  sliding-window layers only the in-window band of chunks is
                  visited, making the cost O(S·W) instead of O(S²).

The Pallas TPU kernel (kernels/flash_attention.py) implements the same
contract; `repro.kernels.ops.attention` dispatches to it when enabled.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .layers import apply_rope, rms_norm

NEG_INF = -1e30


def init_attention(key, cfg, dtype):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(qd)
    p = {
        "wq": (jax.random.normal(ks[0], (d, qd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kvd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kvd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (qd, d)) * so).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,))
        p["k_norm"] = jnp.zeros((cfg.head_dim,))
    return p


def _mask_value(scores, q_pos, k_pos, window: Optional[int]):
    """Causal (+ optional sliding-window) mask, positions broadcastable."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, scores, NEG_INF)


def _running_max(m, s):
    """Online-softmax shift: the running row max of the scores.

    The output does not depend on the shift, so no gradient flows through
    it (as in ``jax.nn.logsumexp``).  Differentiating ``jnp.max`` divides
    by the count of entries equal to the max; where XLA computes the
    scores once for the max and again for that comparison, the two can
    round differently on a TPU, the count is 0, and every query and key
    gradient becomes NaN.
    """
    return jnp.maximum(m, jax.lax.stop_gradient(jnp.max(s, axis=-1)))


def _softcap(scores, cap: Optional[float]):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def attention_xla(q, k, v, q_pos, k_pos, *, window=None, softcap=None):
    """q: (B,Sq,H,D), k/v: (B,Sk,Hkv,D); returns (B,Sq,H,D)."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    scores = _mask_value(scores, q_pos, k_pos, window)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def attention_chunked_unrolled(q, k, v, q_pos, k_pos, *, window=None,
                               softcap=None, chunk_q: int = 2048,
                               chunk_k: int = 2048):
    """Flash-style attention with a PYTHON loop over (q-chunk, kv-chunk)
    pairs, visiting only causally/within-window reachable pairs.

    Used by the dry-run (cfg.scan_unroll): every chunk body appears in the
    HLO, so ``cost_analysis`` FLOP/byte totals are exact (XLA counts scan
    bodies once).  Assumes q and k positions are aligned ranges (training /
    prefill), which holds for every dry-run shape.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    scale = 1.0 / np.sqrt(d)
    pad_q, pad_k = (-sq) % chunk_q, (-sk) % chunk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_k), constant_values=2 ** 30)
    nq, nk = q.shape[1] // chunk_q, k.shape[1] // chunk_k
    out_chunks = []
    for qi in range(nq):
        q_blk = q[:, qi * chunk_q:(qi + 1) * chunk_q]
        qp = q_pos[qi * chunk_q:(qi + 1) * chunk_q]
        acc = jnp.zeros((b, h, chunk_q, d), jnp.float32)
        m = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, chunk_q), jnp.float32)
        # causal: kv chunk start ≤ q chunk end; window: within band
        hi = min(((qi + 1) * chunk_q + chunk_k - 1) // chunk_k, nk)
        lo = 0
        if window is not None:
            lo = max(0, (qi * chunk_q - window) // chunk_k)
        for kj in range(lo, hi):
            k_blk = _repeat_kv(k[:, kj * chunk_k:(kj + 1) * chunk_k], n_rep)
            v_blk = _repeat_kv(v[:, kj * chunk_k:(kj + 1) * chunk_k], n_rep)
            kp = k_pos[kj * chunk_k:(kj + 1) * chunk_k]
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk).astype(
                jnp.float32) * scale
            s = _softcap(s, softcap)
            s = _mask_value(s, qp, kp, window)
            m_new = _running_max(m, s)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
            m = m_new
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out_chunks.append(jnp.transpose(out, (0, 2, 1, 3)))
    out = jnp.concatenate(out_chunks, axis=1)
    return out[:, :sq].astype(q.dtype)


def attention_chunked(q, k, v, q_pos, k_pos, *, window=None, softcap=None,
                      chunk_q: int = 256, chunk_k: int = 256):
    """Flash-style attention, O(chunk_q·chunk_k) live scores.

    For sliding-window layers only the band of KV chunks that can intersect
    the window is visited per query chunk (static band width).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    n_rep = h // hkv
    scale = 1.0 / np.sqrt(d)

    # pad to chunk multiples
    pad_q = (-sq) % chunk_q
    pad_k = (-sk) % chunk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_k), constant_values=2 ** 30)
    nq, nk = q.shape[1] // chunk_q, k.shape[1] // chunk_k

    qc = q.reshape(b, nq, chunk_q, h, d)
    kc = k.reshape(b, nk, chunk_k, hkv, d)
    vc = v.reshape(b, nk, chunk_k, hkv, d)
    qpc = q_pos.reshape(nq, chunk_q)
    kpc = k_pos.reshape(nk, chunk_k)

    # band of kv chunks per query chunk (static count)
    if window is not None:
        n_band = min(nk, (window + chunk_q) // chunk_k + 2)
    else:
        n_band = nk

    def per_qchunk(qi, q_blk, qp_blk):
        # kv chunk indices to visit: last n_band chunks ending at qi's end
        # (causal ⇒ kv chunk index ≤ roughly qi·chunk_q/chunk_k)
        hi = jnp.minimum((qi + 1) * chunk_q // chunk_k, nk)  # exclusive
        start = jnp.maximum(hi - n_band, 0)

        def inner(carry, j):
            acc, m, l = carry
            kj = jnp.clip(start + j, 0, nk - 1)
            k_blk = jax.lax.dynamic_index_in_dim(kc, kj, axis=1, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vc, kj, axis=1, keepdims=False)
            kp_blk = jax.lax.dynamic_index_in_dim(kpc, kj, axis=0, keepdims=False)
            k_r = _repeat_kv(k_blk, n_rep)
            v_r = _repeat_kv(v_blk, n_rep)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_r).astype(jnp.float32) * scale
            s = _softcap(s, softcap)
            s = _mask_value(s, qp_blk, kp_blk, window)
            # mask out-of-range chunk visits entirely
            s = jnp.where((start + j) < hi, s, NEG_INF)
            m_new = _running_max(m, s)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_r.dtype), v_r).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, chunk_q, d), jnp.float32)
        m0 = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(inner, (acc0, m0, l0), jnp.arange(n_band))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.transpose(out, (0, 2, 1, 3))  # (b, chunk_q, h, d)

    out = jax.lax.map(
        lambda args: per_qchunk(*args),
        (jnp.arange(nq), jnp.moveaxis(qc, 1, 0), qpc))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * chunk_q, h, d)
    return out[:, :sq].astype(q.dtype)


class KVCache(NamedTuple):
    """KV cache; for sliding-window layers S_max = window and the buffer is
    a ring (absolute positions tracked in ``pos``)."""
    k: jnp.ndarray       # (B, S_max, Hkv, D)
    v: jnp.ndarray
    pos: jnp.ndarray     # (S_max,) absolute position of each slot (−big = empty)
    length: jnp.ndarray  # scalar int32 — total tokens seen


class QuantKVCache(NamedTuple):
    """int8 KV cache (per-token-per-head symmetric scales) — halves the
    decode working set vs bf16; the paper's compression idea applied to the
    serving state (beyond-paper §Perf iteration)."""
    k: jnp.ndarray        # int8 (B, S_max, Hkv, D)
    v: jnp.ndarray
    k_scale: jnp.ndarray  # f32 (B, S_max, Hkv)
    v_scale: jnp.ndarray
    pos: jnp.ndarray
    length: jnp.ndarray


def _kv_quant(x):
    """x (B,S,H,D) → int8 codes + per-(B,S,H) scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / safe[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale.astype(jnp.float32)


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_kv_cache(batch: int, s_max: int, n_kv: int, head_dim: int, dtype,
                  quantized: bool = False):
    # empty slots carry a far-future sentinel so the causal mask hides them
    pos = jnp.full((s_max,), 2 ** 30, jnp.int32)
    length = jnp.zeros((), jnp.int32)
    if quantized:
        return QuantKVCache(
            k=jnp.zeros((batch, s_max, n_kv, head_dim), jnp.int8),
            v=jnp.zeros((batch, s_max, n_kv, head_dim), jnp.int8),
            k_scale=jnp.zeros((batch, s_max, n_kv), jnp.float32),
            v_scale=jnp.zeros((batch, s_max, n_kv), jnp.float32),
            pos=pos, length=length)
    return KVCache(
        k=jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
        pos=pos, length=length)


def attention_block(params, cfg, x, *, rope_cs=None, positions=None,
                    window=None, cache: Optional[KVCache] = None,
                    backend: str = "chunked"):
    """Full attention sub-block: qkv proj → rope → attend → out proj.

    Training / prefill: x is (B, S, D), cache is None (train) or an empty
    cache to fill (prefill).  Decode: x is (B, 1, D) and cache holds history.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if rope_cs is not None:
        cos, sin = rope_cs
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)

    if cache is None:
        q_pos = k_pos = positions
        k_all, v_all = k, v
        new_cache = None
    else:
        quant = isinstance(cache, QuantKVCache)
        s_max = cache.k.shape[1]
        start = cache.length
        q_pos = start + jnp.arange(s)
        if s > s_max:
            # prefill longer than a sliding-window ring: keep last s_max
            k_w, v_w = k[:, -s_max:], v[:, -s_max:]
            pos_w = q_pos[-s_max:].astype(jnp.int32)
            if quant:
                kq, ks = _kv_quant(k_w)
                vq, vs = _kv_quant(v_w)
                new_cache = QuantKVCache(k=kq, v=vq, k_scale=ks, v_scale=vs,
                                         pos=pos_w, length=start + s)
            else:
                new_cache = KVCache(k=k_w.astype(cache.k.dtype),
                                    v=v_w.astype(cache.v.dtype),
                                    pos=pos_w, length=start + s)
            # attention over the full fresh sequence (chunked-banded below)
            k_all, v_all, k_pos = k, v, q_pos
        else:
            idx = start % s_max if s == 1 else start  # ring writes for decode
            upd = lambda buf, new, ax=1: jax.lax.dynamic_update_slice_in_dim(
                buf, new, idx, axis=ax)
            pos_all = upd(cache.pos, q_pos.astype(jnp.int32), 0)
            if quant:
                kq, ks = _kv_quant(k)
                vq, vs = _kv_quant(v)
                new_cache = QuantKVCache(
                    k=upd(cache.k, kq), v=upd(cache.v, vq),
                    k_scale=upd(cache.k_scale, ks),
                    v_scale=upd(cache.v_scale, vs),
                    pos=pos_all, length=start + s)
                k_all = _kv_dequant(new_cache.k, new_cache.k_scale, q.dtype)
                v_all = _kv_dequant(new_cache.v, new_cache.v_scale, q.dtype)
            else:
                k_all = upd(cache.k, k.astype(cache.k.dtype))
                v_all = upd(cache.v, v.astype(cache.v.dtype))
                new_cache = KVCache(k=k_all, v=v_all, pos=pos_all,
                                    length=start + s)
                k_all = k_all.astype(q.dtype)
                v_all = v_all.astype(q.dtype)
            k_pos = pos_all

    if backend == "xla":
        fn = attention_xla
    elif cfg.scan_unroll:  # dry-run costing: exact, loop-free HLO
        fn = partial(attention_chunked_unrolled, chunk_q=2048, chunk_k=2048)
    else:
        fn = partial(attention_chunked, chunk_q=min(cfg.chunk_size, max(s, 16)),
                     chunk_k=cfg.chunk_size)
    if s == 1 and cache is not None:
        # decode: single query — use streaming over the cache (no q chunking)
        out = _decode_attention(q, k_all, v_all, q_pos, k_pos, window=window,
                                softcap=cfg.attn_logit_softcap)
    else:
        out = fn(q, k_all, v_all, q_pos, k_pos, window=window,
                 softcap=cfg.attn_logit_softcap)
    out = out.reshape(b, s, cfg.q_dim) @ params["wo"]
    return out, new_cache


def _decode_attention(q, k, v, q_pos, k_pos, *, window=None, softcap=None):
    """One-token decode: q (B,1,H,D) vs full cache (B,S,Hkv,D) — O(S)."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    scores = _mask_value(scores, q_pos, k_pos, window)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
