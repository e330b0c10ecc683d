"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (casts Mosaic
has no lowering for, unaligned slices, VMEM over-use).  Each test lowers
one kernel with ``interpret=False`` at a real width, compiles it for one
chip of a described ``v5e:2x2`` topology, and checks that the kernel is in
the program (``tpu_custom_call``).  Nothing runs: no result or time comes
from here.

The topology and everything built from it live in module-scoped fixtures,
so importing this file loads no TPU library and every worker collects the
same tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compress_pipeline import quant_pipeline, sign_pipeline
from repro.kernels.erasure_mask import erasure_mask
from repro.kernels.pack_bits import pack_bits, unpack_bits
from repro.kernels.quantize_ef import quantize_ef

#: one StableLM-2-1.6B MLP weight (d_model × d_ff)
LEAF = (2048, 5632)
N = LEAF[0] * LEAF[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("levels", [255, 10])
def test_quant_pipeline_compiles(one_chip, dtype, levels):
    x = _spec(one_chip, LEAF, dtype)
    fn = functools.partial(quant_pipeline, levels=levels, vmin=-1.0,
                           vmax=1.0, interpret=False)
    compiled = jax.jit(fn).lower(x, x).compile()
    _assert_kernel(compiled)


def test_sign_pipeline_compiles(one_chip):
    x = _spec(one_chip, LEAF, jnp.float32)
    fn = functools.partial(sign_pipeline, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, x).compile())


@pytest.mark.parametrize("bits", [8, 10])
def test_pack_bits_compiles(one_chip, bits):
    x = _spec(one_chip, (N,), jnp.uint32)
    fn = functools.partial(pack_bits, bits=bits, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x).compile())


@pytest.mark.parametrize("bits", [8, 10])
def test_unpack_bits_compiles(one_chip, bits):
    words = _spec(one_chip, (N // 32 * bits,), jnp.uint32)
    fn = functools.partial(unpack_bits, bits=bits, n=N, interpret=False)
    _assert_kernel(jax.jit(fn).lower(words).compile())


def test_erasure_mask_compiles(one_chip):
    words = _spec(one_chip, (N // 32 * 8,), jnp.uint32)
    fn = functools.partial(erasure_mask, p=0.1, seed=3, interpret=False)
    _assert_kernel(jax.jit(fn).lower(words).compile())


@pytest.mark.parametrize("levels", [255, 1023])   # uint8 and uint16 wire
def test_quantize_ef_compiles(one_chip, levels):
    x = _spec(one_chip, LEAF, jnp.float32)
    fn = functools.partial(quantize_ef, levels=levels, vmin=-0.25,
                           vmax=0.25, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, x).compile())


def test_deploy_round_step_compiles_with_kernels(one_chip, monkeypatch):
    """The deploy round asks ``kernels.ops._interpret`` whether to run its
    kernels compiled; steering that one function makes the whole round
    lower its fused uplink as TPU kernels."""
    from repro.configs import ARCHS, smoke_variant
    from repro.core.deploy import DeployFedLT
    from repro.data.synthetic import make_batch
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = smoke_variant(ARCHS["stablelm-1.6b"])
    alg = DeployFedLT(cfg=cfg, n_epochs=1, pack_wire=True)
    n_agents = 2
    state = jax.eval_shape(lambda: alg.init(jax.random.PRNGKey(0), n_agents))
    batch = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[make_batch(cfg, jax.random.PRNGKey(i), 2, 64)
          for i in range(n_agents)]))
    place = lambda t: jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), t)
    compiled = jax.jit(alg.round_step, donate_argnums=0).lower(
        place(state), place(batch)).compile()
    _assert_kernel(compiled)
