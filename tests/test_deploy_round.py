"""Deploy-mode federated round: correctness on the host device.

Verifies the mesh-shardable ``DeployFedLT.round_step``:
  * loss decreases over rounds (local training works through the round);
  * the compressed round tracks the uncompressed round within the EF bound;
  * EF caches stay bounded;
  * with compression off and one agent, the round reduces to plain
    prox-anchored training (x == y_hat fixed point drift check).
"""
import jax
import jax.numpy as jnp

from repro.core.deploy import DeployFedLT
from repro.data.synthetic import make_batch
from repro.models.config import ModelConfig

CFG = ModelConfig(name="deploy-test", arch_type="dense", n_layers=2,
                  d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                  vocab_size=128, max_seq=128, chunk_size=32,
                  tie_embeddings=True, dtype="float32")


def _batches(n_agents, rounds_key, batch=2, seq=32):
    keys = [jax.random.fold_in(rounds_key, i) for i in range(n_agents)]
    per = [make_batch(CFG, k, batch, seq) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


def test_round_reduces_loss():
    alg = DeployFedLT(cfg=CFG, n_epochs=2, gamma=0.05, rho=10.0,
                      compress=True, levels=1023, vmin=-0.5, vmax=0.5)
    state = alg.init(jax.random.PRNGKey(0), 2)
    step = jax.jit(lambda s, b: alg.round_step(s, b))
    batch = _batches(2, jax.random.PRNGKey(5))
    losses = []
    for k in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    for leaf in jax.tree_util.tree_leaves(state):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


def test_compressed_tracks_uncompressed():
    batch = _batches(2, jax.random.PRNGKey(6))
    states = {}
    for compress in (False, True):
        alg = DeployFedLT(cfg=CFG, n_epochs=2, gamma=0.05, rho=10.0,
                          compress=compress, levels=65535, vmin=-2.0, vmax=2.0)
        st = alg.init(jax.random.PRNGKey(0), 2)
        step = jax.jit(lambda s, b: alg.round_step(s, b))
        for _ in range(4):
            st, _ = step(st, batch)
        states[compress] = st
    # fine quantization (65535 levels over ±2) ⇒ y_hat nearly identical
    d = jax.tree_util.tree_map(lambda a, b: jnp.max(jnp.abs(a - b)),
                               states[False].y_hat, states[True].y_hat)
    max_dev = max(float(x) for x in jax.tree_util.tree_leaves(d))
    assert max_dev < 1e-2


def test_quorum_survivor_mask():
    """``survivors=`` (the host-side quorum close, repro.faults): the
    coordinator mean covers survivors only; an excluded agent's wire is
    dropped and its uplink EF cache reverts to the full corrected
    message (erasure semantics), so nothing is silently discarded."""
    batch = _batches(2, jax.random.PRNGKey(8))
    alg = DeployFedLT(cfg=CFG, n_epochs=1, gamma=0.05, rho=10.0,
                      compress=True, levels=255, vmin=-4.0, vmax=4.0)
    state = alg.init(jax.random.PRNGKey(0), 2)
    all_in = jnp.array([True, True])
    st_all, m_all = alg.round_step(state, batch, survivors=all_in)
    st_none, _ = alg.round_step(state, batch)
    # a full quorum is exactly the unmasked round
    for a, b in zip(jax.tree_util.tree_leaves(st_all.y_hat),
                    jax.tree_util.tree_leaves(st_none.y_hat)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6
    assert float(m_all["quorum_frac"]) == 1.0

    surv = jnp.array([True, False])
    st_q, m_q = alg.round_step(state, batch, survivors=surv)
    assert float(m_q["quorum_frac"]) == 0.5
    # excluded agent: cache reverted to z + c (content kept, not sent)
    z1 = jax.tree_util.tree_leaves(st_q.z)
    c0 = jax.tree_util.tree_leaves(state.c_up)
    c1 = jax.tree_util.tree_leaves(st_q.c_up)
    for z, c_old, c_new in zip(z1, c0, c1):
        assert float(jnp.max(jnp.abs(c_new[1] - (z[1] + c_old[1])))) < 1e-6
    # survivor keeps the normal small EF residual
    for c_new in c1:
        assert float(jnp.max(jnp.abs(c_new[0]))) < 8.0 / 255 + 1e-3
    for leaf in jax.tree_util.tree_leaves(st_q.y_hat):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_ef_caches_bounded():
    # range generously covers the z dynamics → cache stays within one step
    alg = DeployFedLT(cfg=CFG, n_epochs=1, gamma=0.05, rho=10.0,
                      compress=True, levels=255, vmin=-4.0, vmax=4.0)
    state = alg.init(jax.random.PRNGKey(0), 2)
    step = jax.jit(lambda s, b: alg.round_step(s, b))
    batch = _batches(2, jax.random.PRNGKey(7))
    for _ in range(8):
        state, _ = step(state, batch)
    delta = 8.0 / 255
    # per-coordinate uplink cache must stay within one quantization step
    # when messages are in-range (EF never accumulates unboundedly in-range)
    for leaf in jax.tree_util.tree_leaves(state.c_up):
        assert float(jnp.max(jnp.abs(leaf))) < delta + 1e-3


def test_train_donates_state():
    """``launch.train.train`` compiles the round with the state donated:
    the new state takes over the old one's buffers, which is what lets a
    full-width round fit one chip."""
    from repro.launch.train import train

    out = train(CFG, rounds=2, agents=2, batch=1, seq=32, pack_wire=True)
    n_state = 3 * 2 * out["n_params"] + 2 * out["n_params"]   # x,z,c_up + ŷ,c_down
    assert out["memory"].alias_size_in_bytes >= 4 * n_state
    assert len(out["losses"]) == len(out["seconds"]) == 2
    assert all(jnp.isfinite(x) for x in out["losses"])


def test_compile_cache_location(monkeypatch):
    from pathlib import Path

    from repro.launch import train as launch_train

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert launch_train.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = launch_train.enable_compile_cache()
        checkout = Path(__file__).resolve().parents[1]
        assert path == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
