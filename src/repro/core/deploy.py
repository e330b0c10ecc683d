"""Deploy mode: one federated round as a single mesh-sharded ``train_step``.

This is the production path the multi-pod dry-run lowers.  Mapping (see
DESIGN.md §3): agents are mesh slices (pods for the big archs, data-axis
slices for the small ones); every per-agent state leaf carries a leading
agent dim A; local training is ``vmap``-ed over it.  The paper's Algorithm 2
runs inside the step:

  1. v = 2·ŷ − z;  N_e prox-gradient epochs on the LM loss   (local training)
  2. z ← z + 2(x − ŷ)
  3. uplink: wire = Q(z + c_up) as *integer* level indices — the cross-agent
     all-gather moves int8/int16, which is the actual wire saving of the
     paper's compression, visible in the dry-run HLO     (uplink EF);
     with ``pack_wire=True`` the indices are further bit-packed into
     b-bit uint32 wire words (``repro.wire`` layout, Pallas kernels in
     ``repro.kernels.pack_bits``) so the gather moves the exact on-wire
     payload
  4. ȳ = mean_A decode(wire);  y = c_down + ȳ
  5. ŷ = decode(Q(y));  c_down = y − ŷ                      (downlink EF)

Partial participation is a host-side decision (the orbit scheduler picks
which satellites run a round); within the lowered step all present agents
participate — exactly how a real constellation executes.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.compression import (quantize_decode, quantize_encode,
                                wire_index_bits)
from ..core.pytree import tree_map
from ..kernels import ops as _ops
from ..kernels.compress_pipeline import quant_pipeline
from ..kernels.pack_bits import _TILE_VALS, pack_bits, unpack_bits
from ..models.transformer import init_params, lm_loss


def emit_round_series(step: int, metrics: dict) -> None:
    """Fold one ``round_step`` metrics dict into the active trace as
    per-round series samples (no-op when tracing is off).

    Host-side by design: the lowered step stays pure, and the float()
    materialization of the loss only happens when a tracer is installed
    — callers that already print the loss pay nothing extra.
    """
    from ..obs.trace import active as _obs_active
    trc = _obs_active()
    if trc is None:
        return
    trc.series("loss", step, float(metrics["loss"]))
    nb = metrics.get("wire_nbytes_per_agent")
    if nb is not None:
        trc.series("wire_nbytes_per_agent", step, float(nb))
    qf = metrics.get("quorum_frac")
    if qf is not None:
        trc.series("quorum_frac", step, float(qf))


class DeployState(NamedTuple):
    x: object        # (A, …) per-agent models
    z: object        # (A, …) auxiliaries
    c_up: object     # (A, …) uplink EF caches
    y_hat: object    # (…)    last broadcast ŷ (replicated coordinator output)
    c_down: object   # (…)    downlink EF cache
    k: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DeployFedLT:
    """Fed-LT round on the mesh. cfg: ModelConfig; quantization is the
    paper's uniform quantizer with static [vmin, vmax] (wire = level ints)."""

    cfg: object
    n_epochs: int = 2
    gamma: float = 0.02
    rho: float = 10.0
    # wire format: uint8 level indices over a range that must cover the z
    # dynamics (out-of-range values clip, and the EF cache then grows until
    # they re-enter range — pick the range generously, EF absorbs coarse Δ)
    levels: int = 255          # → uint8 wire
    vmin: float = -1.0
    vmax: float = 1.0
    compress: bool = True
    # pack the uplink ints into b-bit uint32 wire words (repro.wire layout,
    # Pallas kernels) before the cross-agent gather — the collective then
    # moves b = ceil(log2(levels+1)) bits/scalar instead of the container
    # dtype's 8/16.  Leaves smaller than one kernel tile (32768 values)
    # gather as plain ints: there the tile padding would exceed the
    # packing saving.
    pack_wire: bool = False
    # run quantize + EF + pack as ONE fused Pallas sweep per tile-sized
    # leaf (repro.kernels.compress_pipeline) instead of the separate
    # quantize_encode → subtract → pack_bits dispatches: the intermediate
    # integer tensor never round-trips through HBM.  Packed words are
    # bit-identical either way; only the dispatch count changes.
    fuse_pipeline: bool = True
    backend: str = "chunked"

    @property
    def wire_word_bits(self) -> int:
        return wire_index_bits(self.levels)

    # -- state ------------------------------------------------------------
    def init(self, key, n_agents: int) -> DeployState:
        p0 = init_params(key, self.cfg)
        stack = lambda t: tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_agents,) + a.shape).copy(), t)
        zeros = lambda t: tree_map(jnp.zeros_like, t)
        xa = stack(p0)
        # z gets buffers of its own so that a round may donate the state
        return DeployState(x=xa, z=stack(p0), c_up=zeros(xa), y_hat=p0,
                           c_down=zeros(p0), k=jnp.zeros((), jnp.int32))

    # -- one round ----------------------------------------------------------
    def round_step(self, state: DeployState, batch,
                   agent_replicate_spec=None, survivors=None):
        """batch: pytree with leading agent dim A on every leaf.

        ``survivors`` (optional ``(A,)`` bool) is the quorum mask the
        host-side round-deadline scheduler hands down (``repro.faults``):
        a round closed at its deadline aggregates only the agents whose
        uplinks landed in time.  Excluded agents still train locally,
        but their wire is dropped from the coordinator mean and their
        uplink EF cache *reverts* to the full corrected message — the
        erasure semantics of ``fedlt_sat._revert_lost_wires``, so the
        straggler's content telescopes into its next landed round
        instead of vanishing.  ``None`` keeps the all-participate
        behavior (and the lowered HLO) unchanged."""
        cfg = self.cfg
        inv_rho = 1.0 / self.rho
        surv = None if survivors is None else jnp.asarray(survivors)

        def _mask(x):
            return surv.reshape((-1,) + (1,) * (x.ndim - 1))

        def local_train(x_i, v_i, batch_i):
            def epoch(w, _):
                loss, g = jax.value_and_grad(
                    lambda q: lm_loss(q, cfg, batch_i, backend=self.backend))(w)
                w = tree_map(
                    lambda wl, gl, vl: (wl - self.gamma *
                                        (gl + inv_rho * (wl - vl)).astype(wl.dtype)),
                    w, g, v_i)
                return w, loss

            if getattr(self.cfg, "scan_unroll", False):
                # dry-run costing: python loop so the epoch backward is
                # unrolled too (scan transposes are loops XLA counts once)
                w, loss = x_i, jnp.zeros((), jnp.float32)
                for _ in range(self.n_epochs):
                    w, loss = epoch(w, None)
                return w, loss
            w, losses = jax.lax.scan(epoch, x_i, None, length=self.n_epochs)
            return w, losses[-1]

        # jax.named_scope: names the round's stages inside jaxprs/HLO and
        # jax.profiler traces — the device-side counterpart of the host
        # spans repro.obs records (annotations survive jit; no-ops
        # otherwise)
        with jax.named_scope("fedlt.local_train"):
            v = tree_map(lambda y, z: (2.0 * y - z).astype(z.dtype),
                         state.y_hat, state.z)
            x_new, last_loss = jax.vmap(local_train)(state.x, v, batch)
            z_new = tree_map(lambda z, xn, y: z + 2.0 * (xn - y),
                             state.z, x_new, state.y_hat)

        # ---- uplink: quantize + EF; integer tensor crosses the slow link --
        if self.compress:
            bits = self.wire_word_bits
            interp = _ops._interpret()

            def _fused_uplink(z, c, **kw):
                with jax.named_scope("fedlt.uplink.fused_pipeline"):
                    return quant_pipeline(z, c, **kw)

            def uplink_leaf(z, c, spec):
                """One parameter tensor through uplink EF + wire: returns
                (gathered wire floats, new EF cache).

                Tile-sized leaves with ``pack_wire`` take the FUSED
                quantize→EF→pack sweep (one Pallas dispatch, packed words
                bit-identical to the separate path); ``fuse_pipeline=False``
                keeps the separate quantize_encode → subtract → pack_bits
                dispatches.  Leaves below one kernel tile (32768 values)
                gather as plain ints either way: there the tile padding
                would exceed the packing saving.
                """
                if (self.pack_wire and self.fuse_pipeline
                        and z.size >= _TILE_VALS):
                    words, newc = _fused_uplink(
                        z, c, levels=self.levels, vmin=self.vmin,
                        vmax=self.vmax, interpret=interp)
                    if spec is not None:
                        words = jax.lax.with_sharding_constraint(words, P(None))
                    idx = unpack_bits(words, bits, z.size, interpret=interp)
                    g = quantize_decode(idx, self.levels, self.vmin,
                                        self.vmax, z.dtype).reshape(z.shape)
                    return g, newc
                m = z + c
                w = quantize_encode(m, self.levels, self.vmin, self.vmax)
                newc = m - quantize_decode(w, self.levels, self.vmin,
                                           self.vmax, m.dtype)
                if self.pack_wire and w.size >= _TILE_VALS:
                    p = pack_bits(w, bits, interpret=interp)
                    if spec is not None:
                        p = jax.lax.with_sharding_constraint(p, P(None))
                    w = unpack_bits(p, bits, w.size, interpret=interp
                                    ).astype(w.dtype).reshape(w.shape)
                elif spec is not None:
                    # replicate the agent dim of the INT tensor (int8 gather)
                    w = jax.lax.with_sharding_constraint(w, spec)
                g = quantize_decode(w, self.levels, self.vmin, self.vmax,
                                    m.dtype)
                return g, newc

            leaves_z, treedef = jax.tree_util.tree_flatten(z_new)
            leaves_c = treedef.flatten_up_to(state.c_up)
            specs = (treedef.flatten_up_to(agent_replicate_spec)
                     if agent_replicate_spec is not None
                     else [None] * len(leaves_z))
            with jax.named_scope("fedlt.uplink"):
                pairs = [uplink_leaf(z, c, s)
                         for z, c, s in zip(leaves_z, leaves_c, specs)]
            if surv is not None:
                # quorum close: drop excluded wires from the mean, revert
                # their EF cache to the full corrected message (newc + ŵ
                # == z + c — GroupedEFChannel.revert's leaf analogue)
                pairs = [(jnp.where(_mask(g), g, 0.0).astype(g.dtype),
                          jnp.where(_mask(nc), nc, z + c).astype(nc.dtype))
                         for (g, nc), z, c
                         in zip(pairs, leaves_z, leaves_c)]
            gathered = treedef.unflatten([g for g, _ in pairs])
            c_up_new = treedef.unflatten([nc for _, nc in pairs])
            with jax.named_scope("fedlt.aggregate"):
                if surv is not None:
                    n_surv = jnp.maximum(jnp.sum(surv), 1)
                    z_bar = tree_map(
                        lambda g: (jnp.sum(g, axis=0)
                                   / n_surv.astype(g.dtype)), gathered)
                else:
                    z_bar = tree_map(lambda g: jnp.mean(g, axis=0), gathered)
        else:
            c_up_new = state.c_up
            with jax.named_scope("fedlt.aggregate"):
                if surv is not None:
                    n_surv = jnp.maximum(jnp.sum(surv), 1)
                    z_bar = tree_map(
                        lambda z: (jnp.sum(jnp.where(_mask(z), z, 0.0)
                                           .astype(z.dtype), axis=0)
                                   / n_surv.astype(z.dtype)), z_new)
                else:
                    z_bar = tree_map(lambda z: jnp.mean(z, axis=0), z_new)

        # ---- coordinator aggregate + downlink EF --------------------------
        with jax.named_scope("fedlt.downlink"):
            y = tree_map(lambda c, zb: c + zb.astype(c.dtype),
                         state.c_down, z_bar)
            if self.compress:
                y_int = tree_map(
                    lambda m: quantize_encode(m, self.levels, self.vmin,
                                              self.vmax), y)
                y_hat = tree_map(
                    lambda w, m: quantize_decode(w, self.levels, self.vmin,
                                                 self.vmax, m.dtype),
                    y_int, y)
                c_down_new = tree_map(jnp.subtract, y, y_hat)
            else:
                y_hat, c_down_new = y, state.c_down

        new_state = DeployState(x=x_new, z=z_new, c_up=c_up_new, y_hat=y_hat,
                                c_down=c_down_new, k=state.k + 1)
        metrics = {"loss": jnp.mean(last_loss)}
        if surv is not None:
            n_agents = surv.shape[0]
            metrics["quorum_frac"] = (jnp.sum(surv).astype(jnp.float32)
                                      / jnp.float32(n_agents))
        if self.compress:
            # exact measured uplink size per agent under the wire codec
            # (static shapes → a compile-time constant in the metrics)
            from ..wire.codecs import QuantCodec
            codec = QuantCodec(self.levels, self.vmin, self.vmax)
            template = tree_map(lambda x: x[0], state.x)
            metrics["wire_nbytes_per_agent"] = jnp.float32(
                codec.tree_nbytes(template))
        return new_state, metrics
