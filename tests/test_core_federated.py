"""Behaviour tests for the federated core: convergence, EF, baselines.

These validate the paper's central claims at reduced scale:
  * Fed-LT converges exactly without compression (Prop. 1 with δ=1).
  * Error feedback improves the asymptotic optimality error under coarse
    quantization (Table 1).
  * Coarser compression ⇒ larger asymptotic error (§3.1 remark).
  * Baselines behave as in Table 2 (FedAvg/FedProx drift floor; 5GCS exact;
    LED exact at full participation).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.baselines import LED, FedAvg, FedProx, FiveGCS
from repro.core.compression import Identity, UniformQuantizer
from repro.core.error_feedback import EFChannel
from repro.core.fedlt import FedLT, optimality_error
from repro.data.logistic import generate, make_local_loss, solve_global

N, M, D = 30, 150, 30


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    data, _ = generate(key, n_agents=N, m=M, dim=D)
    loss = make_local_loss(eps=50.0, n_agents=N)
    xbar = solve_global(data, eps=50.0)
    return data, loss, xbar


def _run_fedlt(problem, uplink, downlink, rounds, participation=1.0,
               gamma=0.05, rho=0.5, n_epochs=10):
    data, loss, xbar = problem
    alg = FedLT(loss=loss, n_epochs=n_epochs, gamma=gamma, rho=rho,
                uplink=uplink, downlink=downlink)
    st = alg.init(jnp.zeros((D,)), N)
    st, _ = jax.jit(
        lambda s: alg.run(s, data, rounds, jax.random.PRNGKey(1),
                          participation=participation))(st)
    return float(optimality_error(st.x, xbar)), st


def test_fedlt_exact_convergence_no_compression(problem):
    err, _ = _run_fedlt(problem, EFChannel(Identity()), EFChannel(Identity()), 200)
    assert err < 1e-8


def test_fedlt_partial_participation_converges(problem):
    err, _ = _run_fedlt(problem, EFChannel(Identity()), EFChannel(Identity()),
                        400, participation=0.5)
    assert err < 1e-6


def test_fedlt_state_no_nans_under_coarse_quantization(problem):
    C = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    err, st = _run_fedlt(problem, EFChannel(C), EFChannel(C), 100)
    for leaf in jax.tree_util.tree_leaves(st):
        assert jnp.all(jnp.isfinite(leaf))


def test_error_feedback_improves_asymptotic_error(problem):
    """Paper Table 1: Algorithm 2 (EF) beats Algorithm 1 (no EF).

    Tuned in the slow local-training regime where the closed loop low-passes
    the EF-induced dither (see EXPERIMENTS.md §Table-1 for the analysis).
    """
    C = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    kw = dict(rounds=600, gamma=0.002, rho=10.0)
    err_noef, _ = _run_fedlt(problem, EFChannel(C, enabled=False),
                             EFChannel(C, enabled=False), **kw)
    err_ef, _ = _run_fedlt(problem, EFChannel(C, enabled=True),
                           EFChannel(C, enabled=True), **kw)
    assert err_ef < err_noef


def test_coarser_quantization_larger_error(problem):
    kw = dict(rounds=400, gamma=0.002, rho=10.0)
    C_fine = UniformQuantizer(levels=1000, vmin=-10, vmax=10, clip=True)
    C_coarse = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    err_fine, _ = _run_fedlt(problem, EFChannel(C_fine), EFChannel(C_fine), **kw)
    err_coarse, _ = _run_fedlt(problem, EFChannel(C_coarse), EFChannel(C_coarse), **kw)
    assert err_fine < err_coarse


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------

def _run_baseline(problem, alg, rounds, participation=1.0):
    data, loss, xbar = problem
    st = alg.init(jnp.zeros((D,)), N)
    st, _ = jax.jit(
        lambda s: alg.run(s, data, rounds, jax.random.PRNGKey(2),
                          participation=participation))(st)
    return float(optimality_error(st.x, xbar))


def test_fedavg_has_client_drift_floor(problem):
    data, loss, xbar = problem
    err = _run_baseline(problem, FedAvg(loss=loss, n_epochs=10, gamma=0.05), 300)
    assert 1e-4 < err < 10.0  # converges to a biased neighbourhood


def test_fedlt_beats_fedavg_uncompressed(problem):
    data, loss, _ = problem
    err_avg = _run_baseline(problem, FedAvg(loss=loss, n_epochs=10, gamma=0.05), 300)
    err_lt, _ = _run_fedlt(problem, EFChannel(Identity()), EFChannel(Identity()), 300)
    assert err_lt < err_avg


def test_fedprox_reduces_drift_vs_fedavg(problem):
    data, loss, _ = problem
    err_avg = _run_baseline(problem, FedAvg(loss=loss, n_epochs=10, gamma=0.05), 300)
    err_prox = _run_baseline(
        problem, FedProx(loss, n_epochs=10, gamma=0.05, prox_mu=1.0), 300)
    assert err_prox < err_avg


def test_5gcs_exact_convergence(problem):
    data, loss, _ = problem
    alg = FiveGCS(loss=loss, n_epochs=10, gamma=0.05, gamma_p=1.0)
    assert _run_baseline(problem, alg, 400) < 1e-8
    assert _run_baseline(problem, alg, 500, participation=0.5) < 1e-6


def test_led_exact_at_full_participation(problem):
    data, loss, _ = problem
    alg = LED(loss=loss, n_epochs=10, gamma=0.01)
    assert _run_baseline(problem, alg, 600) < 1e-3


@pytest.mark.parametrize("fused_uplink", [True, False])
def test_fedlt_round_names_its_stages(problem, fused_uplink):
    """The round's stages carry the scopes the device trace's per-layer
    readers look for, as in ``DeployFedLT.round_step``."""
    data, loss, _ = problem
    q = UniformQuantizer(levels=10, vmin=-1, vmax=1, clip=True)
    alg = FedLT(loss=loss, n_epochs=2, gamma=0.05, rho=0.5,
                uplink=EFChannel(q), downlink=EFChannel(q),
                fused_uplink=fused_uplink)
    st = alg.init(jnp.zeros((D,)), N)
    text = jax.jit(alg.round).lower(
        st, data, jnp.ones((N,), bool),
        jax.random.PRNGKey(1)).as_text(debug_info=True)
    for scope in ("fedlt.aggregate", "fedlt.downlink", "fedlt.local_train",
                  "fedlt.uplink"):
        assert f"/{scope}/" in text, scope
