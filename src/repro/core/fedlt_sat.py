"""Fed-LTSat (paper Algorithm 3) — the space-ified federated runner.

Algorithm 3 = Algorithm 2 (Fed-LT + compression + EF) with the active set
S_k chosen by the orbit-aware scheduler and uplinks either direct to a GS
or forwarded over multi-hop ISLs — algebraically identical updates, but
different time/bandwidth accounting, which is what Table 2 measures.

The runner is ALGORITHM-AGNOSTIC (FedLT/FedAvg/FedProx/LED/5GCS) and drives
any of them through the discrete-event engine (``repro.sim.engine``) in one
of two aggregation modes:

  * ``mode="sync"`` — one engine round per communication round: the policy
    schedules gateways + ISL relays, the engine executes the plan, and the
    coordinator aggregates when the last scheduled update lands (the seed
    semantics, now with contact-plan scheduling, dropout, and per-station
    contention).
  * ``mode="async"`` — FedBuff-style buffered asynchrony: satellites train
    and deliver continuously; every ``buffer_size`` landed updates the
    coordinator aggregates once, weighting each satellite's received wire
    by ``(1 + staleness)^(-staleness_alpha)`` where staleness counts the
    aggregations that happened while the update was in flight.  The
    weighting is applied to the coordinator's received-wire state
    (``z_hat`` for FedLT, ``m_hat`` for the baselines) — stale updates are
    shrunk toward the previously received value, exactly the
    staleness-damped server step of FedBuff, without touching the
    algorithms themselves.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..constellation.links import message_bytes
from ..faults import quorum_close_time
from ..obs.trace import active as _obs_active
from ..obs.trace import span as obs_span
from .compression import Compressor
from .error_feedback import resync_cache
from .pytree import tree_map, tree_size, tree_split_keys, tree_where_mask


@dataclasses.dataclass
class RoundLog:
    round: int
    time: float            # wall-clock seconds since start
    bytes_up: float        # cumulative uplink bytes over GS links (air
    #                        bytes: with a lossy channel this counts
    #                        retransmissions and truncated attempts too)
    n_active: int          # updates the coordinator actually received
    error: Optional[float] = None
    staleness: Optional[float] = None   # async: mean staleness this round
    n_lost: int = 0        # attempted uplinks the channel destroyed
    bytes_isl: float = 0.0  # cumulative ISL bytes (in-orbit aggregation)


@dataclasses.dataclass(frozen=True, eq=False)
class SpaceRunner:
    """Drives any federated algorithm through the constellation simulator.

    ``engine`` is a :class:`repro.sim.engine.Engine`; a bare
    :class:`~repro.constellation.scheduler.Scheduler` is also accepted and
    wrapped in an engine over its own single-station scenario.

    With a lossy channel (``channel=`` here or on the engine's scenario),
    sync rounds distinguish *attempted* from *delivered* uplinks: lost
    satellites still train and pay air time, the coordinator's received
    wire reverts, and — with ``loss_robust=True`` and an EF-caching
    algorithm — the uplink residual reverts too, so the cached content
    telescopes into the next successful transmission instead of being
    discharged into a wire that never landed
    (:func:`_revert_lost_wires`).
    """

    engine: object
    wire_bits: float = 32.0      # nominal fallback (no-codec compressors)
    mode: str = "sync"           # "sync" | "async"
    buffer_size: int = 8         # async: aggregate every M landed updates
    staleness_alpha: float = 0.5  # async: wire weight (1+s)^(-alpha)
    compressor: Optional[Compressor] = None  # → measured WireMessage bytes
    # lossy channel (repro.channel.ChannelModel): installed on the engine;
    # an engine whose Scenario already carries one needs no argument here
    channel: Optional[object] = None
    # loss-robust error feedback (sync mode): when the channel destroys an
    # uplink, the satellite's EF residual reverts instead of being
    # discharged into the lost wire — the cached content telescopes into
    # the next successful transmission instead of vanishing.  Needs an
    # algorithm with an uplink cache (``c_up``).
    loss_robust: bool = True
    # byte measurement:
    #   "probe"  — encode ONE representative message up front; every
    #              delivery is accounted at that size (seed behavior)
    #   "cohort" — account each sync round from the actually-transmitted
    #              wire state, grouped per contact-window cohort (engine
    #              Cohorts): quant codecs cost out analytically per
    #              update (their sizes are shape-static), sparse codecs
    #              encode each update so content-dependent sizes are
    #              exact — ties in TopK or zeros in RandD shrink the
    #              accounted payload below the nominal fraction·n
    measure: str = "probe"       # "probe" | "cohort" (sync mode only)
    # node-level fault injection (repro.faults.FaultModel): installed on
    # the engine; an engine whose Scenario already carries one needs no
    # argument here.  Crashed satellites lose their in-flight update AND
    # their EF residual (resync_cache) — unlike erasures, where
    # loss_robust keeps the residual telescoping forward.
    faults: Optional[object] = None
    # round deadline with quorum (sync mode): the round closes at
    # t0 + deadline provided ≥ quorum·attempted update-weights landed
    # (else it extends to the quorum-completing landing); deliveries past
    # the close are stragglers, treated as erasures so their content
    # folds into the next round via EF.  None = wait for the last
    # scheduled delivery (historical behavior).
    deadline: Optional[float] = None
    quorum: float = 0.0

    def __post_init__(self):
        if hasattr(self.engine, "select") and not hasattr(self.engine, "run_round"):
            object.__setattr__(self, "engine", self.engine._engine())
        if self.channel is not None:
            # install on the (mutable) engine so every transmission the
            # engine commits runs through the lossy-channel ARQ; the
            # engine's install path also invalidates its ChannelCache
            # memo, which may hold ARQ plans for the previous channel
            if hasattr(self.engine, "install_channel"):
                self.engine.install_channel(self.channel)
            else:                            # wrapped non-Engine stand-ins
                self.engine.channel = self.channel
                self.engine._refresh_blocked()
        if self.faults is not None:
            if hasattr(self.engine, "install_faults"):
                self.engine.install_faults(self.faults)
            else:                            # wrapped non-Engine stand-ins
                self.engine.faults = self.faults
                self.engine._refresh_blocked()
        if self.mode not in ("sync", "async"):
            raise ValueError(f"mode must be 'sync' or 'async', got {self.mode!r}")
        if self.deadline is not None:
            if self.mode != "sync":
                raise ValueError(
                    "deadline/quorum round closing is sync-only — async "
                    "FedBuff aggregation has no round boundary to close")
            if self.deadline <= 0.0:
                raise ValueError(f"deadline must be > 0: {self.deadline}")
        if not 0.0 <= self.quorum <= 1.0:
            raise ValueError(f"quorum must be in [0,1]: {self.quorum}")
        if self.measure not in ("probe", "cohort"):
            raise ValueError(
                f"measure must be 'probe' or 'cohort', got {self.measure!r}")
        if self.measure == "cohort" and self.mode == "async":
            raise ValueError(
                "measure='cohort' needs per-round RoundResults and is sync-"
                "only; async runs account deliveries at the probe size")
        topo = getattr(self.engine, "topology", None)
        if topo is not None and getattr(topo, "kind", "direct") != "direct":
            if self.mode == "async":
                raise ValueError(
                    "mode='async' needs topology='direct' — plane "
                    "aggregation has no free-running merge point")
            if self.measure == "cohort":
                raise ValueError(
                    "measure='cohort' groups per-satellite wires by "
                    "contact window; plane topologies uplink one merged "
                    "wire per head — use measure='probe'")

    # -- shared setup ------------------------------------------------------
    def _msg_bytes(self, state) -> float:
        """On-wire size of one per-agent update.

        With a ``compressor`` whose wire codec exists, one representative
        per-agent message is actually encoded (``repro.wire``) and the
        exact ``WireMessage.nbytes`` — bit-packed payload + headers —
        drives every engine transmission time and ``bytes_up`` log.  The
        nominal ``wire_bits`` estimate is only the fallback for
        compressors without a codec.
        """
        if self.compressor is not None and \
                self.compressor.wire_codec() is not None:
            from ..wire import measure_tree_bytes  # lazy: wire imports core
            # encode one representative message: a random probe with the
            # per-agent shapes, run through the compressor (zeros — e.g.
            # the init state — would make sparse codecs count an empty
            # payload)
            template = tree_map(lambda x: x[0], state.x)
            keys = tree_split_keys(jax.random.PRNGKey(0), template)
            probe = tree_map(
                lambda k_, t: jax.random.normal(k_, t.shape, t.dtype),
                keys, template)
            wire = self.compressor(jax.random.PRNGKey(1), probe)
            return measure_tree_bytes(self.compressor, wire)
        n_params = tree_size(state.x) // jax.tree_util.tree_leaves(
            state.x)[0].shape[0]
        return message_bytes(n_params, self.wire_bits)

    def run(self, alg, state, data, n_rounds: int, key,
            error_fn: Optional[Callable] = None,
            log_every: int = 10, ckpt=None, ckpt_every: int = 1,
            resume: bool = False) -> tuple:
        """Drive ``n_rounds`` rounds.  ``ckpt`` (a
        :class:`repro.checkpoint.run.RunCheckpoint`) checkpoints the run
        every ``ckpt_every`` sync rounds; ``resume=True`` restarts from
        the newest intact checkpoint and continues bit-identically to an
        uninterrupted run (sync mode only — the async delivery stream
        has no round boundary to checkpoint at)."""
        if self.mode == "async":
            if ckpt is not None or resume:
                raise ValueError("checkpoint/resume is sync-only")
            return self._run_async(alg, state, data, n_rounds, key,
                                   error_fn, log_every)
        return self._run_sync(alg, state, data, n_rounds, key,
                              error_fn, log_every, ckpt=ckpt,
                              ckpt_every=ckpt_every, resume=resume)

    def _cohort_nbytes(self, state, cohorts) -> dict:
        """Measured on-wire bytes per satellite, grouped per cohort.

        Quant codecs have shape-static sizes, so each update is costed
        analytically (``tree_nbytes`` of one satellite's slice — no
        encode needed; the transmit-side *compute* for a cohort is the
        fused kernel benchmarked in ``benchmarks/sim_scale.py`` and
        exercised by ``FedLT(fused_uplink=True)``, not re-run here).
        Sparse codecs encode each update from the actually-transmitted
        wire state so content-dependent payload sizes are exact.
        """
        from ..wire.codecs import QuantCodec  # lazy: wire imports core
        codec = self.compressor.wire_codec()
        wire_field = "z_hat" if hasattr(state, "z_hat") else "m_hat"
        tree = getattr(state, wire_field)
        template = tree_map(lambda x: x[0], tree)
        static_nb = (float(codec.tree_nbytes(template))
                     if isinstance(codec, QuantCodec) else None)
        out: dict = {}
        for cohort in cohorts:
            if static_nb is not None:
                for s in cohort.sats:
                    out[s] = static_nb
                continue
            idx = np.asarray(cohort.sats)
            sub = tree_map(lambda x: x[idx], tree)
            for i, s in enumerate(cohort.sats):
                one = tree_map(lambda x: x[i], sub)
                out[s] = float(codec.encode(one).nbytes)
        return out

    # -- synchronous rounds ------------------------------------------------
    def _run_sync(self, alg, state, data, n_rounds, key, error_fn, log_every,
                  ckpt=None, ckpt_every: int = 1, resume: bool = False):
        msg = self._msg_bytes(state)
        use_cohorts = (self.measure == "cohort" and self.compressor is not None
                       and self.compressor.wire_codec() is not None)
        channel = getattr(self.engine, "channel", None)
        wire_field = "z_hat" if hasattr(state, "z_hat") else "m_hat"
        has_cache = hasattr(state, "c_up")
        dispatch = _round_dispatch(alg, key, n_rounds)
        t, up_bytes, isl_bytes = 0.0, 0.0, 0.0
        logs: List[RoundLog] = []
        trc = _obs_active()       # read once; None ⇒ tracing fully off
        start_k = 0
        if ckpt is not None and resume:
            loaded = ckpt.load(like=state)
            if loaded is not None:
                # bit-identical continuation: per-round keys come from the
                # same split (_round_dispatch), engine rounds are pure
                # functions of (scenario, seed, t0), and the time cursor /
                # accumulators restore exactly — so rounds ≥ start_k replay
                # the uninterrupted run's floats
                state, meta = loaded
                start_k = int(meta.get("k_next", 0))
                t = float(meta.get("t", 0.0))
                up_bytes = float(meta.get("up_bytes", 0.0))
                isl_bytes = float(meta.get("isl_bytes", 0.0))
                logs = [RoundLog(**d) for d in meta.get("logs", [])]
                if hasattr(self.engine, "_round_idx"):
                    self.engine._round_idx = start_k   # trace round labels
                if trc is not None:
                    # replay the prefix's ledger curves so a resumed
                    # trace carries the full bit-identical series
                    trc.event("resume", k_next=start_k, t=float(t),
                              bytes_up=float(up_bytes))
                    for lg in logs:
                        trc.series("bytes_up", lg.round, lg.bytes_up)
                        if lg.error is not None:
                            trc.series("e_K", lg.round, lg.error)
        for k in range(start_k, n_rounds):
            with obs_span("runner.round", round=k):
                with obs_span("runner.schedule"):
                    res = self.engine.run_round(t, msg)
                t_round0 = t
                delivered = res.mask
                attempted = np.zeros_like(delivered)
                merged = getattr(res, "merged", None)
                if merged is not None:
                    # in-orbit aggregation: one head delivery stands for every
                    # member it merged — they all trained and their wires all
                    # crossed ISLs, so a lost head wire loses (and, below,
                    # reverts) the whole plane
                    for d in res.deliveries:
                        attempted[list(merged[d.sat])] = True
                else:
                    for d in res.deliveries:
                        attempted[d.sat] = True
                aborted = getattr(res, "aborted", None)
                if aborted is not None:
                    # updates destroyed in-orbit with no delivery record
                    # (head-failover collateral): attempted-but-lost
                    attempted = attempted | aborted
                crashed = getattr(res, "crashed", None)
                duration = res.duration
                if self.deadline is not None:
                    # quorum round closing: the coordinator stops waiting at
                    # t_close; anything landing later is a straggler whose
                    # wire (and, with loss_robust, residual) reverts below —
                    # its content folds into the next round via EF
                    landed = [(d.t_done,
                               len(merged[d.sat]) if merged is not None else 1)
                              for d in res.deliveries if d.delivered]
                    t_close = quorum_close_time(
                        t_round0, self.deadline, self.quorum, landed,
                        int(attempted.sum()))
                    late = np.zeros_like(delivered)
                    for d in res.deliveries:
                        if d.delivered and d.t_done > t_close:
                            if merged is not None:
                                late[list(merged[d.sat])] = True
                            else:
                                late[d.sat] = True
                    delivered = delivered & ~late
                    duration = max(t_close - t_round0, 0.0)
                lost = attempted & ~delivered
                lossy = bool(lost.any())
                # with a lossy channel the satellites that transmitted-but-lost
                # still trained and paid the uplink: they participate in the
                # round, then the coordinator-side wire is reverted below
                # (the coordinator can only know what actually landed)
                active_np = attempted if lossy else delivered
                with obs_span("runner.dispatch"):
                    state_new, _ = dispatch(state, data, active_np, k)
                # what each satellite actually put on the air this round — for
                # lost satellites that is the PRE-revert wire, so cohort byte
                # accounting below must measure this state, not the final one
                tx_state = state_new
                if lossy:
                    absorb = self.loss_robust and has_cache
                    with obs_span("runner.revert"):
                        state_new = _revert_lost_wires(
                            state_new, state, wire_field, jnp.asarray(lost),
                            absorb=absorb)
                    if trc is not None:
                        # resid_norm: ‖c_up[lost]‖ after the revert — the EF
                        # content kept telescoping instead of vanishing
                        lost_idx = np.nonzero(lost)[0]
                        norm2 = 0.0
                        if has_cache:
                            for leaf in jax.tree_util.tree_leaves(state_new.c_up):
                                arr = np.asarray(leaf[lost_idx], dtype=np.float64)
                                norm2 += float((arr * arr).sum())
                        trc.event("ef_revert", round=k, n_lost=int(lost.sum()),
                                  sats=[int(s) for s in lost_idx],
                                  absorb=bool(absorb),
                                  resid_norm=float(np.sqrt(norm2)))
                        trc.metrics.counter("ef_reverts").add(float(lost.sum()))
                        trc.series("ef_resid_norm", k, float(np.sqrt(norm2)))
                if crashed is not None and bool(crashed.any()) and has_cache:
                    # crash semantics: the rebooted sat's memory is gone, so
                    # the erasure revert above (which KEEPS the residual) is
                    # overridden for crashed rows — c_up re-syncs to zero
                    with obs_span("runner.revert"):
                        state_new = state_new._replace(
                            c_up=resync_cache(state_new.c_up, crashed))
                    if trc is not None:
                        trc.event("ef_resync", round=k,
                                  n_crashed=int(crashed.sum()),
                                  sats=[int(s) for s in np.nonzero(crashed)[0]])
                        trc.metrics.counter("ef_resyncs").add(
                            float(crashed.sum()))
                state = state_new
                t += duration
                # bytes_up = what actually crossed the GS links this round —
                # air bytes, i.e. retransmissions and truncated attempts count
                if use_cohorts:
                    per_sat = self._cohort_nbytes(tx_state, res.cohorts())
                    if channel is not None:
                        up_bytes += sum(
                            per_sat[d.sat] * (d.nbytes_attempted / msg)
                            for d in res.deliveries)
                    else:
                        up_bytes += sum(per_sat.values())
                else:
                    up_bytes += sum(d.nbytes_attempted for d in res.deliveries)
                isl_bytes += float(getattr(res, "bytes_isl", 0.0))
                err = (float(error_fn(state))
                       if error_fn is not None and (k % log_every == 0
                                                    or k == n_rounds - 1) else None)
                logs.append(RoundLog(k, t, up_bytes, int(delivered.sum()), err,
                                     n_lost=int(lost.sum()),
                                     bytes_isl=isl_bytes))
                if trc is not None:
                    # downlink ledger: the coordinator rebroadcasts the model
                    # to every satellite it scheduled (not modeled by the
                    # engine's uplink timeline, so accounted here)
                    down = trc.metrics.counter("bytes_down")
                    down.add(msg * float(res.scheduled.sum()))
                    plane_kw = ({} if merged is None
                                else dict(bytes_isl=float(isl_bytes)))
                    trc.event("fl_round", round=k, t0=float(t_round0),
                              t=float(t), bytes_up=float(up_bytes),
                              n_active=int(delivered.sum()),
                              n_lost=int(lost.sum()),
                              error=err if err == err else None,
                              mode="sync", **plane_kw)
                    # first-class convergence/byte curves for the run ledger
                    trc.series("bytes_up", k, up_bytes)
                    trc.series("bytes_down", k, down.total)
                    if merged is not None:
                        trc.series("bytes_isl_cum", k, isl_bytes)
                    n_att = int(attempted.sum())
                    trc.series("lost_frac", k,
                               float(lost.sum()) / n_att if n_att else 0.0)
                    # quorum/fault observability: who made it into this
                    # round's aggregate, and what fraction of the attempted
                    # cohort that is (1.0 on a healthy deadline-less round)
                    n_surv = int(delivered.sum())
                    trc.series("survivors", k, float(n_surv))
                    trc.series("quorum_frac", k,
                               n_surv / n_att if n_att else 1.0)
                    if err is not None and err == err:
                        trc.series("e_K", k, err)
                if ckpt is not None and ((k + 1) % ckpt_every == 0
                                         or k == n_rounds - 1):
                    ckpt.save_round(state, step=k + 1, t=t, up_bytes=up_bytes,
                                    isl_bytes=isl_bytes, logs=logs)
        return state, logs

    # -- buffered-async (FedBuff-style) -------------------------------------
    def _run_async(self, alg, state, data, n_rounds, key, error_fn, log_every):
        msg = self._msg_bytes(state)
        dispatch = _round_dispatch(alg, key, n_rounds)
        n_agents = jax.tree_util.tree_leaves(state.x)[0].shape[0]
        wire_field = "z_hat" if hasattr(state, "z_hat") else "m_hat"

        trc = _obs_active()       # read once; None ⇒ tracing fully off
        with obs_span("runner.schedule"):
            records = self.engine.run_async(
                0.0, msg, n_deliveries=n_rounds * self.buffer_size)
        # only landed updates feed the aggregator; with a lossy channel the
        # record list also holds failed attempts, whose air bytes still
        # count toward the uplink ledger below
        deliveries = [d for d in records if d.delivered]
        rec_ptr = 0
        agg_times: List[float] = []
        logs: List[RoundLog] = []
        up_bytes = 0.0
        for k in range(n_rounds):
            chunk = deliveries[k * self.buffer_size:(k + 1) * self.buffer_size]
            if not chunk:
                break           # windows ran dry before n_rounds aggregations
            active_np = np.zeros(n_agents, dtype=bool)
            stale = np.zeros(n_agents, dtype=np.float64)
            for d in chunk:
                active_np[d.sat] = True
                stale[d.sat] = len(agg_times) - bisect.bisect_right(
                    agg_times, d.t_start)
            weights = np.where(active_np,
                               (1.0 + stale) ** (-self.staleness_alpha), 1.0)
            with obs_span("runner.dispatch"):
                new_state, _ = dispatch(state, data, active_np, k)
                state = _damp_wires(new_state, state, wire_field,
                                    jnp.asarray(weights))
            t0_agg = chunk[0].t_start
            t = chunk[-1].t_done
            agg_times.append(t)
            n_lost_win = 0
            while rec_ptr < len(records) and records[rec_ptr].t_done <= t:
                up_bytes += records[rec_ptr].nbytes_attempted
                n_lost_win += not records[rec_ptr].delivered
                rec_ptr += 1
            err = (float(error_fn(state))
                   if error_fn is not None and (k % log_every == 0
                                                or k == n_rounds - 1) else None)
            mean_stale = float(stale[active_np].mean())
            logs.append(RoundLog(k, t, up_bytes, int(active_np.sum()), err,
                                 staleness=mean_stale))
            if trc is not None:
                hist = trc.metrics.histogram("staleness", lo=0.0)
                for d in chunk:
                    hist.observe(float(stale[d.sat]))
                down = trc.metrics.counter("bytes_down")
                down.add(msg * float(active_np.sum()))
                trc.event("fl_round", round=k, t0=float(t0_agg),
                          t=float(t), bytes_up=float(up_bytes),
                          n_active=int(active_np.sum()),
                          n_lost=n_lost_win, staleness=mean_stale,
                          error=err if err == err else None,
                          mode="async")
                # first-class convergence/byte curves for the run ledger
                trc.series("bytes_up", k, up_bytes)
                trc.series("bytes_down", k, down.total)
                trc.series("staleness", k, mean_stale)
                n_win = len(chunk) + n_lost_win
                trc.series("lost_frac", k,
                           n_lost_win / n_win if n_win else 0.0)
                if err is not None and err == err:
                    trc.series("e_K", k, err)
        return state, logs


def _round_dispatch(alg, key, n_rounds: int) -> Callable:
    """``dispatch(state, data, active, k)``: round ``k`` of a run, with
    ``active`` the host's bool mask and the round's key
    ``jax.random.split(key, n_rounds)[k]``, bit for bit (resume relies on
    it).

    The keys come to the host once, before the first round.  Each round
    then packs its host inputs, the mask (0/1) followed by the key's data,
    into one ``uint32`` buffer, moves it to the device in one explicit
    transfer and runs one program, the jitted round, which unpacks it:
    indexing a device array of keys by a host ``k`` would cost two more
    programs and two more transfers a round.  Typed keys
    (``jax.random.key``) travel as their key data and are re-wrapped with
    their own implementation.
    """
    keys = jax.random.split(key, n_rounds)
    typed = jnp.issubdtype(keys.dtype, jax.dtypes.prng_key)
    impl = jax.random.key_impl(keys) if typed else None
    host_keys = np.asarray(jax.random.key_data(keys) if typed else keys)
    width = host_keys.shape[-1]

    @jax.jit
    def packed_round(state, data, buf):
        round_key = buf[-width:]
        if typed:
            round_key = jax.random.wrap_key_data(round_key, impl=impl)
        return alg.round(state, data, buf[:-width] != 0, round_key)

    def dispatch(state, data, active, k: int):
        buf = np.empty(active.shape[0] + width, np.uint32)
        buf[:-width] = active
        buf[-width:] = host_keys[k]
        return packed_round(state, data, jax.device_put(buf))

    return dispatch


def _revert_lost_wires(new_state, old_state, field: str, lost,
                       *, absorb: bool):
    """Coordinator-side fix-up for channel-destroyed uplinks.

    The round ran with the lost satellites active (they trained and
    transmitted), but the coordinator never received their wire: its
    received-wire slot (``z_hat``/``m_hat``) reverts to the previous
    value.

    With ``absorb=True`` (loss-robust EF) the satellite's uplink residual
    also reverts: ``c_up ← c_up_old``.  The EF cache update
    ``c ← (msg + c_old) − wire`` discharges the cached residual into the
    wire — legitimate only if the wire *lands*.  Reverting on loss keeps
    the residual (plus the quantization error it was carrying) in the
    cache, so the lost round's content telescopes into the agent's next
    successful transmission exactly as if the round had never been
    scheduled; per-agent, EF runs over the subsequence of successful
    uplinks, which is what the paper's telescoping argument (§2.2) needs.
    Without the revert (``absorb=False`` — naive lossy EF) the cache
    wrongly believes the wire was delivered and the residual vanishes
    from the bookkeeping.
    """
    wire_new = getattr(new_state, field)
    wire_old = getattr(old_state, field)
    out = new_state._replace(
        **{field: tree_where_mask(lost, wire_old, wire_new)})
    if absorb:
        out = out._replace(c_up=tree_where_mask(lost, old_state.c_up,
                                                new_state.c_up))
    return out


def _damp_wires(new_state, old_state, field: str, weights):
    """Staleness-weighted server step: blend the coordinator's received
    wires between this round's value and the previous one, per agent.
    Agents whose wire did not change this round are unaffected (blend is a
    no-op when new == old)."""
    new_wire = getattr(new_state, field)
    old_wire = getattr(old_state, field)

    def blend(nw, ow):
        w = weights.reshape((-1,) + (1,) * (nw.ndim - 1))
        return w * nw + (1.0 - w) * ow

    return new_state._replace(**{field: tree_map(blend, new_wire, old_wire)})
