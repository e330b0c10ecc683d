"""Sharding rules: unit tests (no multi-device mesh needed — specs only).

Uses an abstract mesh over 1 device? No — PartitionSpec construction needs
real axis sizes, so we build the production mesh shape with AbstractMesh.
"""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.launch.sharding import batch_specs, cache_specs, param_specs
from repro.models.transformer import init_cache, init_params

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _shapes(cfg):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def test_embed_and_mlp_rules():
    p = _shapes(ARCHS["stablelm-1.6b"])
    specs = param_specs(p, MESH, agent_axes=())
    assert specs["embed"]["table"] == P("model", "data")
    assert specs["scan"][0]["mlp"]["up"] == P(None, "data", "model")
    assert specs["scan"][0]["mlp"]["down"] == P(None, "model", "data")
    assert specs["final_norm"] == P(None)


def test_mqa_kv_sharding_follows_divisibility():
    """granite kv=1 (kv_dim=128): divisible by model=16 → sharded; a
    hypothetical 24-wide dim would be replicated."""
    p = _shapes(ARCHS["granite-20b"])
    specs = param_specs(p, MESH, agent_axes=())
    assert specs["scan"][0]["attn"]["wk"] == P(None, "data", "model")
    odd = {"scan": ({"attn": {"wk": jax.ShapeDtypeStruct((1, 24, 24),
                                                         jnp.float32)}},)}
    specs_odd = param_specs(odd, MESH, agent_axes=())
    assert specs_odd["scan"][0]["attn"]["wk"] == P(None, None, None)


def test_moe_expert_stack_rules():
    p = _shapes(ARCHS["mixtral-8x7b"])
    specs = param_specs(p, MESH, agent_axes=())
    assert specs["scan"][0]["moe"]["up"] == P(None, None, "data", "model")
    assert specs["scan"][0]["moe"]["down"] == P(None, None, "model", "data")


def test_agent_stacked_tp_only():
    p = _shapes(ARCHS["rwkv6-3b"])
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((16,) + s.shape, s.dtype), p)
    specs = param_specs(stacked, MESH, agent_axes=("data",), fsdp=None)
    assert specs["embed"]["table"] == P("data", "model", None)
    assert specs["scan"][0]["rwkv"]["wr"] == P("data", None, None, "model")


def test_multipod_pod_agents():
    p = _shapes(ARCHS["mixtral-8x7b"])
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), p)
    specs = param_specs(stacked, MESH3, agent_axes=("pod",), stacked=True)
    assert specs["scan"][0]["moe"]["up"] == P("pod", None, None, "data", "model")


def test_batch_specs_shapes():
    batch = {"tokens": jax.ShapeDtypeStruct((16, 16, 4096), jnp.int32)}
    specs = batch_specs(batch, MESH, agent_axes=("data",), stacked=True)
    assert specs["tokens"] == P("data", None, None)
    batch2 = {"tokens": jax.ShapeDtypeStruct((32, 32768), jnp.int32)}
    specs2 = batch_specs(batch2, MESH, agent_axes=())
    assert specs2["tokens"] == P("data", None)


def test_cache_specs_long_context_seq_sharding():
    cfg = ARCHS["gemma3-27b"]
    shapes = jax.eval_shape(
        lambda: init_cache(cfg, 1, s_max=524288, dtype=jnp.bfloat16))
    specs = cache_specs(shapes, MESH, shard_batch=False)
    # global-layer KV (slot index 5 = "attn"): seq sharded over data
    kv_spec = specs["scan"][5].k
    assert kv_spec == P(None, None, "data", "model", None)


def test_cache_specs_batch_sharding():
    cfg = ARCHS["stablelm-1.6b"]
    shapes = jax.eval_shape(
        lambda: init_cache(cfg, 128, s_max=32768, dtype=jnp.bfloat16))
    specs = cache_specs(shapes, MESH, shard_batch=True)
    assert specs["scan"][0].k == P(None, "data", None, "model", None)
