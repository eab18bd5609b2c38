"""The Pallas kernels of the serving path compile for a TPU v5e.

Each test lowers a kernel with ``interpret=False`` at qwen1.5-0.5b's
widths for one chip of a described (not attached) ``v5e:2x2`` topology
and checks that the compiled program holds the Mosaic kernel. Nothing
runs, so these guard only what the chip's compiler would refuse: tile
alignment, fast-memory limits, lowering errors.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import ops

D_MODEL, D_FF, VOCAB = 1024, 2816, 151936   # qwen1.5-0.5b
SLICING = (4, 2, 2)
ROWS = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [D_FF, VOCAB], ids=["d_ff", "lm_head"])
def test_centered_int8_matmul_compiles(one_chip, no_compile_cache, n):
    fn = jax.jit(lambda x, w, c: ops.centered_int8_matmul(
        x, w, c, backend="pallas-tpu"))
    compiled = fn.lower(_spec((8, D_MODEL), jnp.int8, one_chip),
                        _spec((D_MODEL, n), jnp.int8, one_chip),
                        _spec((n,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


def _crossbar_args(one_chip):
    n_seg = D_MODEL // ROWS
    return (_spec((8, D_MODEL), jnp.int32, one_chip),
            _spec((len(SLICING), n_seg, ROWS, D_FF), jnp.int8, one_chip),
            _spec((n_seg, D_FF), jnp.int32, one_chip))


def test_fused_crossbar_compiles(one_chip, no_compile_cache):
    fn = jax.jit(lambda x, planes, centers: ops.fused_crossbar_forward(
        x, planes, (4, 2, 0), centers, input_slicing=(1,) * 8,
        adc_lo=-64, adc_hi=63, backend="pallas-tpu"))
    _assert_kernel(fn.lower(*_crossbar_args(one_chip)).compile())


def test_fused_spec_crossbar_compiles(one_chip, no_compile_cache):
    fn = jax.jit(lambda x, planes, centers: ops.fused_spec_crossbar_forward(
        x, planes, (4, 2, 0), centers, spec_slicing=SLICING,
        adc_lo=-64, adc_hi=63, backend="pallas-tpu"))
    _assert_kernel(fn.lower(*_crossbar_args(one_chip)).compile())


# (slots, heads, KV heads, head_dim, pool blocks, blocks per slot) of the
# chip cells: qwen1.5-0.5b chat and yi-6b batch
PAGED = {"qwen05_chat": (32, 16, 16, 64, 2048, 160),
         "yi6b_batch": (64, 32, 4, 128, 11264, 256)}


@pytest.mark.parametrize("cell", sorted(PAGED))
def test_paged_decode_attention_compiles(one_chip, no_compile_cache, cell):
    B, H, K, D, n_blocks, max_blocks = PAGED[cell]
    pool = _spec((n_blocks + 1, 16, K, D), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v, t, n: ops.paged_decode_attention(
        q, k, v, t, n, sentinel=n_blocks, backend="pallas-tpu"))
    compiled = fn.lower(_spec((B, H, D), jnp.bfloat16, one_chip), pool, pool,
                        _spec((B, max_blocks), jnp.int32, one_chip),
                        _spec((B,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


def test_kernel_compiles_under_two_chip_mesh(topo, no_compile_cache):
    """GSPMD cannot partition a Mosaic kernel; under a mesh the registry
    runs it whole on each device, so a sharded operand still compiles."""
    mesh = jax.sharding.Mesh(topo.devices[:2], ("model",))
    cols = NamedSharding(mesh, PartitionSpec(None, "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    fn = jax.jit(lambda x, w, c: ops.centered_int8_matmul(
        x, w, c, backend="pallas-tpu"))
    with mesh:
        compiled = fn.lower(_spec((8, D_MODEL), jnp.int8, rep),
                            _spec((D_MODEL, D_FF), jnp.int8, cols),
                            _spec((D_FF,), jnp.int32, rep)).compile()
    _assert_kernel(compiled)
