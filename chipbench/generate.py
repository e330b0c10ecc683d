"""The benchmark's inputs and weights, made on the device from ``--seed``.

Each maker takes the seed's key as an argument of its jitted program, so
that one program serves every seed and the compile cache holds it after
the first run.  The token streams copy the order-2 Markov generator of
``data/synthetic.py`` and the logistic data copy ``data/logistic.py``,
so that a later change to the program's own generators cannot change
what the benchmark feeds it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole number up to 64 bits: the low 32 bits make
    the key and the high 32 bits are folded in, so seeds that agree mod
    2**32 still differ."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# token batches for the language-model cells
# ---------------------------------------------------------------------------

def markov_tokens(key, batch: int, seq: int, vocab: int, order_states: int = 64):
    """Tokens of a random sparse order-2 Markov chain (one table per key)."""
    k_tab, k_init, k_samp = jax.random.split(key, 3)
    v_eff = min(vocab, 4096)
    table = jax.random.dirichlet(k_tab, jnp.ones((v_eff,)) * 0.05,
                                 shape=(order_states,))
    state0 = jax.random.randint(k_init, (batch,), 0, order_states)

    def step(state, k):
        tok = jax.random.categorical(k, jnp.log(table[state] + 1e-9), axis=-1)
        return (state * 31 + tok) % order_states, tok

    _, toks = jax.lax.scan(step, state0, jax.random.split(k_samp, seq))
    return jnp.transpose(toks).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("rounds", "agents", "batch",
                                             "seq", "vocab"))
def token_pool(key, *, rounds: int, agents: int, batch: int, seq: int,
               vocab: int):
    """``(rounds, agents, batch, seq)`` int32 tokens: one distinct batch
    per agent and round, each agent with a chain of its own (the
    federated setting's heterogeneous data)."""
    def one(r, a):
        return markov_tokens(jax.random.fold_in(jax.random.fold_in(key, r), a),
                             batch, seq, vocab)

    return jax.vmap(lambda r: jax.vmap(lambda a: one(r, a))(
        jnp.arange(agents)))(jnp.arange(rounds))


# ---------------------------------------------------------------------------
# weights of a dense decoder
# ---------------------------------------------------------------------------

def dense_weights(cfg: dict, key):
    """Weights of one model copy in the layout the program's decoder
    reads: the embedding table, the layers stacked along a leading axis,
    and the norms' offsets from 1 (zero, in float32).  Matrices are
    normal with a fan-in scale, in the configuration's dtype."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    head_dim = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * head_dim
    kv = cfg["num_key_value_heads"] * head_dim
    dtype = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(key, 0x5EED), 8)

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    params = {
        "embed": {"table": (jax.random.normal(ks[0], (cfg["vocab_size"], d))
                            * 0.02).astype(dtype)},
        "scan": ({
            "ln1": jnp.zeros((layers, d), jnp.float32),
            "attn": {"wq": mat(ks[1], (layers, d, q), d),
                     "wk": mat(ks[2], (layers, d, kv), d),
                     "wv": mat(ks[3], (layers, d, kv), d),
                     "wo": mat(ks[4], (layers, q, d), q)},
            "ln2": jnp.zeros((layers, d), jnp.float32),
            "mlp": {"up": mat(ks[5], (layers, d, f), d),
                    "down": mat(ks[6], (layers, f, d), f),
                    "gate": mat(ks[7], (layers, d, f), d)},
        },),
        "tail": (),
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg["tie_word_embeddings"]:
        raise ValueError("an untied head is not laid out here")
    return params


# ---------------------------------------------------------------------------
# the paper's logistic-regression data
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_agents", "m", "dim",
                                             "label_noise"))
def logistic_data(key, *, n_agents: int, m: int, dim: int,
                  label_noise: float = 0.05):
    """Features ~ N(0, I) and labels from a planted model with flipped
    noise: ``{"a": (N, m, dim), "b": (N, m)}`` in float32."""
    k_a, k_w, k_flip = jax.random.split(key, 3)
    a = jax.random.normal(k_a, (n_agents, m, dim))
    w_true = jax.random.normal(k_w, (dim,))
    b = jnp.sign(jnp.einsum("imd,d->im", a, w_true) + 1e-12)
    flip = jax.random.bernoulli(k_flip, label_noise, b.shape)
    return {"a": a, "b": jnp.where(flip, -b, b)}
