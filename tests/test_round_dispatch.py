"""Round dispatch of ``SpaceRunner``: each round's host inputs (the mask
and the round's key) reach the device in one explicit transfer, and the
result is bit-identical to the per-round
``jax.jit(alg.round)(state, data, jnp.asarray(mask), split(key, n)[k])``.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedlt_sat
from repro.sim import Engine

N, DIM, ROUNDS = 20, 16, 5


def _reference_dispatch(alg, key, n_rounds):
    """The explicit per-round reference: the mask and the key sliced from
    the device array of keys, each round."""
    round_fn = jax.jit(alg.round)
    keys = jax.random.split(key, n_rounds)

    def dispatch(state, data, active, k):
        return round_fn(state, data, jnp.asarray(active), keys[k])

    return dispatch


def _recording(make, fed):
    """``make``'s dispatch, with every (round, mask) it is fed kept."""
    def wrapped(alg, key, n_rounds):
        inner = make(alg, key, n_rounds)

        def dispatch(state, data, active, k):
            fed.append((k, np.array(active)))
            return inner(state, data, active, k)
        return dispatch
    return wrapped


class _Killed(Exception):
    pass


class _StopAt:
    """An engine that stops the run at its ``stop``-th call, as a killed
    process would."""

    def __init__(self, engine, stop):
        self._engine, self.stop, self.calls = engine, stop, 0

    def run_round(self, t, msg):
        if self.calls == self.stop:
            raise _Killed
        self.calls += 1
        return self._engine.run_round(t, msg)

    def __getattr__(self, name):
        return getattr(self._engine, name)


def _setup(case):
    from repro.channel import ChannelModel, SelectiveRepeatARQ
    from repro.constellation.orbits import GroundStation, Walker
    from repro.core.compression import RandD, UniformQuantizer
    from repro.core.error_feedback import EFChannel
    from repro.core.fedlt import FedLT
    from repro.data.logistic import generate, make_local_loss
    from repro.sim import Scenario
    data, _ = generate(jax.random.PRNGKey(0), n_agents=N, m=40, dim=DIM)
    q = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    # a stochastic uplink makes every case but the paper's quantizer read
    # the round's key
    up = EFChannel(q) if case == "probe" else EFChannel(RandD(fraction=0.25))
    alg = FedLT(loss=make_local_loss(eps=50.0, n_agents=N), n_epochs=2,
                gamma=0.005, rho=20.0, uplink=up, downlink=EFChannel(q),
                fused_uplink=False)
    compute = (15.0 + 45.0 * (np.arange(N) % 5) / 4.0
               if case == "deadline" else 30.0)
    sc = Scenario(name="small", walker=Walker(n_sats=N, n_planes=4),
                  stations=(GroundStation(),), k_direct=3, n_relay=2,
                  compute_time=compute)
    kw = {}
    if case == "lossy":
        kw["channel"] = ChannelModel(
            loss=0.25, arq=SelectiveRepeatARQ(seg_bytes=16, max_rounds=2))
    elif case == "deadline":
        kw.update(deadline=40.0, quorum=0.25)
    elif case == "async":
        kw.update(mode="async", buffer_size=4)

    def runner():
        return fedlt_sat.SpaceRunner(Engine(sc), compressor=up.compressor,
                                     **kw)
    return runner, alg, alg.init(jnp.zeros((DIM,)), N), data


def _run(case, key, tmp_path):
    from repro.checkpoint.run import RunCheckpoint
    runner, alg, st, data = _setup(case)
    if case != "resume":
        return runner().run(alg, st, data, ROUNDS, key)
    ckpt = RunCheckpoint(str(tmp_path / "ck"))
    with pytest.raises(_Killed):
        r = runner()
        object.__setattr__(r, "engine", _StopAt(r.engine, ROUNDS // 2))
        r.run(alg, st, data, ROUNDS, key, ckpt=ckpt)
    return runner().run(alg, st, data, ROUNDS, key, ckpt=ckpt, resume=True)


@pytest.mark.parametrize("key_kind", ["raw", "typed"])
@pytest.mark.parametrize("case", ["probe", "randd", "lossy", "deadline",
                                  "resume", "async"])
def test_dispatch_bit_identical_to_per_round_reference(case, key_kind,
                                                       tmp_path,
                                                       monkeypatch):
    """The packed dispatch's final state and logs equal those of the same
    run with the reference dispatch, fed the same engine masks; where no
    round lost an update, also those of a plain loop over those masks."""
    key = (jax.random.PRNGKey(7) if key_kind == "raw"
           else jax.random.key(7))
    fed, fed_ref = [], []
    make = fedlt_sat._round_dispatch
    monkeypatch.setattr(fedlt_sat, "_round_dispatch", _recording(make, fed))
    state, logs = _run(case, key, tmp_path / "new")
    monkeypatch.setattr(fedlt_sat, "_round_dispatch",
                        _recording(_reference_dispatch, fed_ref))
    ref_state, ref_logs = _run(case, key, tmp_path / "ref")

    assert [k for k, _ in fed] == [k for k, _ in fed_ref]
    assert all(np.array_equal(a, b) for (_, a), (_, b) in zip(fed, fed_ref))
    assert [dataclasses.asdict(lg) for lg in logs] == \
        [dataclasses.asdict(lg) for lg in ref_logs]
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(ref_state), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if case == "lossy":
        assert any(lg.n_lost for lg in logs)          # the revert ran
    if case == "deadline":
        assert any(lg.n_lost for lg in logs)          # stragglers cut off
    if case == "resume":
        assert [k for k, _ in fed] == list(range(ROUNDS // 2)) + \
            list(range(ROUNDS // 2, ROUNDS))

    if case in ("probe", "randd"):
        assert not any(lg.n_lost for lg in logs)
        _, alg, st, data = _setup(case)
        round_fn = jax.jit(alg.round)
        keys = jax.random.split(key, ROUNDS)
        for k, mask in fed:
            st, _ = round_fn(st, data, jnp.asarray(mask), keys[k])
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(st), strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class _GuardAfterFirst:
    """An engine whose rounds after the first run with every implicit
    transfer disallowed, until ``guard`` is closed."""

    def __init__(self, engine):
        self._engine, self.calls = engine, 0
        self.guard = contextlib.ExitStack()

    def run_round(self, t, msg):
        self.calls += 1
        if self.calls == 2:
            self.guard.enter_context(jax.transfer_guard("disallow"))
        return self._engine.run_round(t, msg)

    def __getattr__(self, name):
        return getattr(self._engine, name)


@pytest.mark.parametrize("case", ["probe", "lossy"])
def test_sync_rounds_make_no_implicit_transfer(case):
    """Past the first round (which compiles), a sync round moves its host
    inputs to the device only by explicit transfers: no device array is
    indexed by a host integer and no host array is handed to the jitted
    round."""
    runner, alg, st, data = _setup(case)
    r = runner()
    engine = _GuardAfterFirst(r.engine)
    object.__setattr__(r, "engine", engine)
    with engine.guard:
        state, logs = r.run(alg, st, data, ROUNDS, jax.random.PRNGKey(3))
        jax.block_until_ready(state)
    assert engine.calls == ROUNDS and len(logs) == ROUNDS
    if case == "lossy":
        assert any(lg.n_lost for lg in logs[1:])      # the revert ran guarded
