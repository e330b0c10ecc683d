"""Fed-LT with bi-directional compression and error feedback.

Simulate mode: Algorithms 1 and 2 of the paper, with all N agents vmapped
over a leading agent axis.  Algorithm 1 (compression, no EF) and Algorithm 2
(compression + EF) are the same code path — pass ``EFChannel(C, enabled=False)``
for Algorithm 1, exactly mirroring the paper's Table-1 ablation.

State layout (leaves carry a leading agent axis N where noted):

    x      (N, …)  per-agent models x_i
    z      (N, …)  per-agent auxiliaries z_i
    c_up   (N, …)  per-agent uplink EF caches c_i
    z_hat  (N, …)  coordinator's last-received uplink wire per agent
                   (what the paper calls z_{i,k−1} for inactive agents —
                   the coordinator can only know what was transmitted)
    c_down (…)     coordinator downlink EF cache c
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .error_feedback import EFChannel
from .pytree import (tree_map, tree_mean_axis0, tree_where_mask,
                     tree_zeros_like)
from ..optim.solvers import local_prox_gd


class FedLTState(NamedTuple):
    x: object
    z: object
    c_up: object
    z_hat: object
    c_down: object
    k: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class FedLT:
    """Algorithm 2 (paper). loss(params, agent_data) -> scalar.

    ``n_epochs`` = N_e, ``gamma`` = local step γ, ``rho`` = ρ.
    """

    loss: Callable
    n_epochs: int = 10
    gamma: float = 0.1
    rho: float = 1.0
    uplink: EFChannel = EFChannel()
    downlink: EFChannel = EFChannel()
    # one fused compress→EF→pack kernel sweep over the whole agent-stacked
    # uplink instead of a vmapped per-satellite add→compress→subtract chain
    # (requires uplink.fusable(): clip=True uniform quantizer with EF on;
    # silently falls back to the vmap path otherwise)
    fused_uplink: bool = False

    # -- setup ------------------------------------------------------------
    def init(self, x0, n_agents: int) -> FedLTState:
        """x0: single-model pytree (no agent axis); replicated to all agents."""
        xN = tree_map(lambda a: jnp.broadcast_to(a[None], (n_agents,) + a.shape).copy(), x0)
        return FedLTState(
            x=xN,
            z=xN,
            c_up=tree_zeros_like(xN),
            z_hat=xN,
            c_down=tree_zeros_like(x0),
            k=jnp.zeros((), jnp.int32),
        )

    # -- one communication round ------------------------------------------
    def round(self, state: FedLTState, data, active, key) -> Tuple[FedLTState, dict]:
        """One iteration of the outer loop.

        data:   pytree with leading agent axis N on every leaf.
        active: bool (N,) — the set S_{k+1} (from Bernoulli sampling or the
                orbit scheduler).
        """
        k_down, k_up = jax.random.split(key)

        # jax.named_scope: the stages' names in the HLO metadata and in
        # jax.profiler traces, as DeployFedLT.round_step names them (no
        # change to the compiled code)
        # ---- coordinator: aggregate + downlink EF (paper lines 3-5) ----
        with jax.named_scope("fedlt.aggregate"):
            y_mean = tree_mean_axis0(state.z_hat)
        with jax.named_scope("fedlt.downlink"):
            y_wire, c_down_new = self.downlink.send(k_down, y_mean,
                                                    state.c_down)

        # ---- agents: local training (paper lines 8-14), vmapped ----
        grad_fn = jax.grad(self.loss)

        def agent_update(x_i, z_i, data_i):
            v_i = tree_map(lambda y, z: 2.0 * y - z, y_wire, z_i)
            w = local_prox_gd(grad_fn, x_i, v_i, data_i,
                              n_epochs=self.n_epochs, gamma=self.gamma, rho=self.rho)
            z_new = tree_map(lambda z, xn, y: z + 2.0 * (xn - y), z_i, w, y_wire)
            return w, z_new

        with jax.named_scope("fedlt.local_train"):
            x_new, z_new = jax.vmap(agent_update)(state.x, state.z, data)

        # partial participation: inactive agents keep x, z (paper line 18)
        x_next = tree_where_mask(active, x_new, state.x)
        z_next = tree_where_mask(active, z_new, state.z)

        # ---- uplink EF + transmit (paper lines 15-16), per agent ----
        n_agents = active.shape[0]
        with jax.named_scope("fedlt.uplink"):
            if self.fused_uplink and self.uplink.fusable():
                # one kernel dispatch per leaf over the full agent stack
                wire, c_up_new = self.uplink.send_fused(z_next, state.c_up)
            else:
                up_keys = jax.random.split(k_up, n_agents)
                wire, c_up_new = jax.vmap(
                    lambda kk, m, c: self.uplink.send(kk, m, c))(
                        up_keys, z_next, state.c_up)
        c_up_next = tree_where_mask(active, c_up_new, state.c_up)
        z_hat_next = tree_where_mask(active, wire, state.z_hat)

        new_state = FedLTState(x=x_next, z=z_next, c_up=c_up_next,
                               z_hat=z_hat_next, c_down=c_down_new,
                               k=state.k + 1)
        info = {"n_active": jnp.sum(active)}
        return new_state, info

    # -- fleet-sharded round (mega-constellation scaling) ------------------
    def round_sharded(self, mesh, n_agents: int) -> Callable:
        """Build a round function whose vmapped agent axis is sharded over
        ``mesh``'s first axis (the "fleet" axis) with ``shard_map``.

        Each device trains its shard of the fleet locally; the only
        cross-device traffic is the coordinator aggregate (one ``psum`` of
        the per-shard z_hat sums) and the replicated downlink — exactly
        the communication pattern of the real system, where ground
        stations exchange aggregated models, not per-satellite state.
        Same signature and semantics as :meth:`round` (up to float
        summation order in the aggregate).  ``n_agents`` must divide by
        the fleet axis size; use
        :func:`repro.launch.sharding.fleet_mesh` which returns ``None``
        on a single device (fall back to :meth:`round` then).
        """
        fleet = mesh.axis_names[0]
        n_dev = mesh.shape[fleet]
        if n_agents % n_dev:
            raise ValueError(
                f"n_agents={n_agents} not divisible by fleet axis {n_dev}")
        grad_fn = jax.grad(self.loss)

        def body(x, z, c_up, z_hat, c_down, k, data, active, k_down,
                 up_keys):
            # coordinator aggregate: local shard sum + one psum
            y_local = tree_map(lambda s: jnp.sum(s, axis=0), z_hat)
            y_mean = tree_map(lambda s: jax.lax.psum(s, fleet) / n_agents,
                              y_local)
            y_wire, c_down_new = self.downlink.send(k_down, y_mean, c_down)

            def agent_update(x_i, z_i, data_i):
                v_i = tree_map(lambda y, zz: 2.0 * y - zz, y_wire, z_i)
                w = local_prox_gd(grad_fn, x_i, v_i, data_i,
                                  n_epochs=self.n_epochs, gamma=self.gamma,
                                  rho=self.rho)
                z_new = tree_map(lambda zz, xn, y: zz + 2.0 * (xn - y),
                                 z_i, w, y_wire)
                return w, z_new

            x_new, z_new = jax.vmap(agent_update)(x, z, data)
            x_next = tree_where_mask(active, x_new, x)
            z_next = tree_where_mask(active, z_new, z)
            if self.fused_uplink and self.uplink.fusable():
                wire, c_up_new = self.uplink.send_fused(z_next, c_up)
            else:
                wire, c_up_new = jax.vmap(
                    lambda kk, m, c: self.uplink.send(kk, m, c))(
                        up_keys, z_next, c_up)
            c_up_next = tree_where_mask(active, c_up_new, c_up)
            z_hat_next = tree_where_mask(active, wire, z_hat)
            n_active = jax.lax.psum(jnp.sum(active), fleet)
            return (x_next, z_next, c_up_next, z_hat_next, c_down_new,
                    k + 1, n_active)

        Pf, Pr = P(fleet), P()
        sharded = jax.shard_map(
            body, mesh=mesh,
            in_specs=(Pf, Pf, Pf, Pf, Pr, Pr, Pf, Pf, Pr, Pf),
            out_specs=(Pf, Pf, Pf, Pf, Pr, Pr, Pr),
            check_vma=False)

        def round_fn(state: FedLTState, data, active, key):
            k_down, k_up = jax.random.split(key)
            up_keys = jax.random.split(k_up, n_agents)
            out = sharded(state.x, state.z, state.c_up, state.z_hat,
                          state.c_down, state.k, data, active, k_down,
                          up_keys)
            return FedLTState(*out[:6]), {"n_active": out[6]}

        return round_fn

    def run(self, state: FedLTState, data, n_rounds: int, key,
            participation: float = 1.0, mesh=None):
        """Convenience driver: Bernoulli(p) participation, jitted scan.

        ``mesh``: optional fleet mesh (see :meth:`round_sharded`) — the
        vmapped agent dimension shards across its devices; ``None`` runs
        the single-device path unchanged.
        """
        n_agents = jax.tree_util.tree_leaves(state.x)[0].shape[0]
        round_impl = (self.round if mesh is None
                      else self.round_sharded(mesh, n_agents))

        def body(st, kk):
            k_act, k_round = jax.random.split(kk)
            active = jax.random.bernoulli(k_act, participation, (n_agents,))
            # guarantee at least one active agent (paper assumes p_i > 0)
            active = active.at[0].set(True) if participation < 1.0 else jnp.ones(
                (n_agents,), bool)
            st, info = round_impl(st, data, active, k_round)
            return st, info

        keys = jax.random.split(key, n_rounds)
        return jax.lax.scan(body, state, keys)


def optimality_error(x_agents, x_star):
    """Paper §3 metric: e_k = Σ_i ‖x_{i,k} − x̄‖²."""
    diffs = tree_map(lambda xa, xs: xa - xs[None], x_agents,
                     x_star)
    return sum(jnp.sum(d * d) for d in jax.tree_util.tree_leaves(diffs))
