"""Plain float32 reference of the federated language-model round.

The decoder is written out as the configuration file states it: token
embedding, per layer an RMS norm with a ``1 + w`` scale (the scale kept
as its offset from 1, as the program keeps it), causal attention with
grouped key/value heads and rotary position over each whole head (its
two halves rotated against each other), a residual, a second norm and a
SiLU-gated MLP, then a final norm and the tied embedding as the head.  The loss is
next-token cross-entropy over the batch.  Around it runs the paper's
Algorithm 2, one agent at a time:

    v   = 2·ŷ − z_i
    x_i ← N_e steps of  w ← w − γ(∇f_i(w) + (w − v)/ρ)
    z_i ← z_i + 2(x_i − ŷ)                      (= 2·x_i − v)
    m   = z_i + c_i;  c_i ← m − Q(m)            (uplink EF)
    y   = c + mean_i Q(m_i);  ŷ = Q(y);  c ← y − ŷ   (downlink EF)

with ``Q`` the uniform quantizer over ``[vmin, vmax]`` with ``levels``
steps and clipping.  Matrix products run at ``Precision.HIGHEST``.
The control (``precision="fp8"``) takes every product's operands in
float8 (e4m3 forward, e5m2 for gradients) with a per-tensor scale and
rounds the state it keeps, and each step of the quantizer, through
scaled e4m3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


#: (exponent bits, mantissa bits, largest value) of the float8 formats.
#: Rounding goes through ``lax.reduce_precision``: a cast to a narrower
#: type and back may be dropped by XLA, which keeps excess precision.
E4M3 = (4, 3, 240.0)
E5M2 = (5, 2, 57344.0)


def _to8(x, fmt):
    """``x`` rounded to a float8 format with a per-tensor scale."""
    e, m, top = fmt
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / top
    return jax.lax.reduce_precision(x / scale, exponent_bits=e,
                                    mantissa_bits=m) * scale


def _mm8_fwd_only(a, b, fmt_a, fmt_b):
    return jnp.matmul(_to8(a, fmt_a), _to8(b, fmt_b), precision=HIGHEST)


@jax.custom_vjp
def _mm8(a, b):
    return _mm8_fwd_only(a, b, E4M3, E4M3)


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    ga = _mm8_fwd_only(g, jnp.swapaxes(b, -1, -2), E5M2, E4M3)
    gb = _mm8_fwd_only(jnp.swapaxes(a, -1, -2), g, E4M3, E5M2)
    # operands broadcast over leading axes: sum the gradient back
    while gb.ndim > b.ndim:
        gb = gb.sum(0)
    return ga.reshape(a.shape), gb.reshape(b.shape)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """x (B, S, H, Dh): rotate each head, its first half against its
    second."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs        # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def logits(params, cfg: dict, tokens, mm=_mm32):
    """(B, S) int tokens → (B, S, V) float32 logits."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    hd = d // heads
    eps = cfg["rms_norm_eps"]
    b, s = tokens.shape
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    table = params["embed"]["table"]
    x = table[tokens]
    layer = params["scan"][0]
    for i in range(cfg["num_hidden_layers"]):
        h = _rms(x, layer["ln1"][i], eps)
        q = mm(h, layer["attn"]["wq"][i]).reshape(b, s, heads, hd)
        k = mm(h, layer["attn"]["wk"][i]).reshape(b, s, kv_heads, hd)
        v = mm(h, layer["attn"]["wv"][i]).reshape(b, s, kv_heads, hd)
        q = _rope(q, pos, cfg["rope_theta"])
        k = _rope(k, pos, cfg["rope_theta"])
        rep = heads // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        qt, kt, vt = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))
        scores = mm(qt, jnp.swapaxes(kt, -1, -2)) / np.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        att = jnp.transpose(mm(probs, vt), (0, 2, 1, 3)).reshape(b, s, d)
        x = x + mm(att, layer["attn"]["wo"][i])
        h2 = _rms(x, layer["ln2"][i], eps)
        gate = mm(h2, layer["mlp"]["gate"][i])
        up = mm(h2, layer["mlp"]["up"][i])
        x = x + mm(jax.nn.silu(gate) * up, layer["mlp"]["down"][i])
    x = _rms(x, params["final_norm"], eps)
    return mm(x, table.T)


def loss(params, cfg: dict, tokens, labels, mm=_mm32):
    """Mean next-token cross-entropy; a label of −1 is left out."""
    lg = logits(params, cfg, tokens, mm)[:, :-1]
    lab = labels[:, 1:]
    valid = lab >= 0
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, jnp.maximum(lab, 0)[..., None],
                                 axis=-1)[..., 0]
    nll = jnp.where(valid, lse - picked, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def quantize(m, levels, vmin, vmax, r=lambda x: x):
    """Uniform quantizer with clipping: the decoded level.  ``r`` rounds
    each intermediate (the identity: exact float32 arithmetic)."""
    delta = (vmax - vmin) / levels
    idx = jnp.floor(r(r(r(r(jnp.clip(m, vmin, vmax)) - vmin) / delta) + 0.5))
    return r(r(jnp.clip(idx, 0, levels) * delta) + vmin)


def _store8(x):
    """Round a float32 array to per-tensor-scaled float8 (e4m3)."""
    return _to8(x, E4M3)


def _store16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm16(a, b):
    return jnp.matmul(_store16(a), _store16(b), precision=HIGHEST)


_exact = lambda x: x

#: precision → (matrix product, rounding of the stored state, rounding in
#: the quantizer's arithmetic)
PRECISIONS = {
    "f32": (_mm32, _exact, _exact),
    "fp8": (_mm8, _store8, _store8),
    "bf16": (_mm16, _store16, _exact),
    "bf16q": (_mm16, _store16, _store16),
}


class Round:
    """The reference's jitted pieces for one configuration.

    ``precision`` is ``"f32"`` for the reference; ``"fp8"`` is the
    control, the precision next below the configuration's bfloat16:
    float8 products, and the kept state and the quantizer's arithmetic
    rounded to scaled float8, as the program keeps its state and does its
    arithmetic in bfloat16.  ``"bf16"`` (bfloat16
    products and state) and ``"bf16q"`` (the quantizer's arithmetic in
    bfloat16 too) serve only as witnesses when a reading is looked
    into."""

    def __init__(self, cfg: dict, alg: dict, n_epochs: int,
                 precision: str = "f32"):
        self.cfg, self.alg, self.n_epochs = cfg, alg, n_epochs
        mm, store, qr = PRECISIONS[precision]
        q = functools.partial(quantize, levels=alg["levels"],
                              vmin=alg["vmin"], vmax=alg["vmax"], r=qr)
        gamma, inv_rho = alg["gamma"], 1.0 / alg["rho"]
        tmap = jax.tree_util.tree_map

        def train(x, y_hat, z, tokens, labels):
            v = tmap(lambda yy, zz: store(2.0 * yy - zz), y_hat, z)

            def epoch(w, _):
                val, g = jax.value_and_grad(loss)(w, cfg, tokens, labels, mm)
                w = tmap(lambda wl, gl, vl: store(
                    wl - gamma * (gl + inv_rho * (wl - vl))), w, g, v)
                return w, val

            w, losses = jax.lax.scan(epoch, x, None, length=n_epochs)
            return w, v, losses[-1]

        def uplink(w, v, c, dec_sum):
            z = tmap(lambda wl, vl: store(2.0 * wl - vl), w, v)
            m = tmap(jnp.add, z, c)
            dec = tmap(q, m)
            return (z, tmap(lambda a, b: store(a - b), m, dec),
                    tmap(jnp.add, dec_sum, dec))

        def downlink(dec_sum, c_down, n_agents):
            y = tmap(lambda cd, s: cd + s / n_agents, c_down, dec_sum)
            y_hat = tmap(q, y)
            return y_hat, tmap(lambda a, b: store(a - b), y, y_hat)

        self.start = jax.jit(lambda p: tmap(lambda x: store(x.astype(jnp.float32)), p))
        self.train = jax.jit(train, donate_argnums=(0, 2))
        self.uplink = jax.jit(uplink, donate_argnums=(1, 2, 3))
        self.downlink = jax.jit(downlink, donate_argnums=(0, 1))

    def run(self, p0, tokens, labels, rounds: int, observe) -> None:
        """Run ``rounds`` rounds from the shared start ``p0`` (a tree on
        the device).  ``tokens``/``labels`` are indexed ``[round, agent]``.
        Calls ``observe(r, agent, x, c_up)`` after each agent's round ``r``
        (1-based) and ``observe(r, None, y_hat, c_down, loss)`` after the
        round's downlink, with trees on the device.

        One agent's state is on the chip at a time; the others wait in
        host memory.  The last agent of a round stays on the chip and goes
        first in the next."""
        tmap = jax.tree_util.tree_map
        n_agents = tokens.shape[1]
        y_hat = self.start(p0)
        c_down = tmap(jnp.zeros_like, y_hat)
        states = [None] * n_agents
        order = list(range(n_agents))
        for r in range(rounds):
            dec_sum = tmap(jnp.zeros_like, y_hat)
            losses = []
            for i, a in enumerate(order):
                if states[a] is None:
                    x, z = self.start(p0), self.start(p0)
                    c = tmap(jnp.zeros_like, y_hat)
                else:
                    x, z, c = (tmap(jnp.asarray, t) for t in states[a])
                states[a] = None
                w, v, lv = self.train(x, y_hat, z, tokens[r, a], labels[r, a])
                losses.append(lv)
                z, c, dec_sum = self.uplink(w, v, c, dec_sum)
                observe(r + 1, a, w, c)
                if r + 1 < rounds:
                    keep = i == n_agents - 1
                    states[a] = (w, z, c) if keep else jax.device_get((w, z, c))
                del w, v, z, c
            y_hat, c_down = self.downlink(dec_sum, c_down, float(n_agents))
            observe(r + 1, None, y_hat, c_down,
                    float(np.mean([float(v) for v in losses])))
            order = order[::-1]
