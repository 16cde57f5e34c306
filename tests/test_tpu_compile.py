"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at real widths for one chip of a
``v5e:2x2`` topology described by the installed TPU compiler and compiles
it, which raises what Mosaic would raise on the chip (block shapes off the
(8, 128) tiling, unsupported reshapes, VMEM overruns).  Shapes are
smollm-135m's (d 576, d_ff 1536, 9 query / 3 KV heads of 64) and the
default fleet MLP task's (32 -> 16 -> 4 in 8x8 tiles).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fleet_fused as FF
from repro.kernels import ops
from repro.models import mlp

D, DFF, H, HKV, HD = 576, 1536, 9, 3, 64
SMOLLM_TILE = (D // 8, DFF // 8)          # auto_tile_grid's 72 x 192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("blocks", [(128, 128), SMOLLM_TILE],
                         ids=["mxu_128", "smollm_72x192"])
@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["fwd", "transposed"])
def test_masked_matmul_compiles(one_chip, blocks, transpose_rhs):
    bk, bn = blocks
    fn = functools.partial(ops.masked_matmul, block_k=bk, block_n=bn,
                           transpose_rhs=transpose_rhs, interpret=False)
    x = _f32(256, DFF if transpose_rhs else D)
    _compile(fn, one_chip, x, _f32(D, DFF),
             _f32(-(-D // bk), -(-DFF // bn)))


@pytest.mark.parametrize("blocks", [(128, 128), SMOLLM_TILE],
                         ids=["mxu_128", "smollm_72x192"])
def test_tile_norms_compiles(one_chip, blocks):
    fn = functools.partial(ops.tile_norms, block_k=blocks[0],
                           block_n=blocks[1], interpret=False)
    _compile(fn, one_chip, _f32(D, DFF))


@pytest.mark.parametrize("cache_len", [512, 64],
                         ids=["block_512", "serve_page_64"])
def test_flash_decode_compiles(one_chip, cache_len):
    fn = functools.partial(ops.flash_decode, interpret=False)
    kv = _f32(4, HKV, cache_len, HD)
    _compile(fn, one_chip, _f32(4, H, HD), kv, kv,
             jax.ShapeDtypeStruct((4,), jnp.int32))


def test_flash_prefill_compiles(one_chip):
    fn = functools.partial(ops.flash_prefill, causal=True, interpret=False)
    kv = _f32(2, HKV, 256, HD)
    _compile(fn, one_chip, _f32(2, 256, H, HD), kv, kv)


def test_fused_fleet_grads_compiles(one_chip):
    params = jax.eval_shape(lambda: mlp.init_mlp_classifier(
        jax.random.PRNGKey(0), 32, (16,), 4))
    c, batch, block = 13, 8, 8
    ws, _ = FF.layer_weights(params)
    keeps = [_f32(c, -(-w.shape[0] // block), -(-w.shape[1] // block))
             for w in ws]
    fn = functools.partial(FF.fused_grads_pallas, block=block,
                           interpret=False)
    _compile(fn, one_chip, params, _f32(c, batch, 32),
             jax.ShapeDtypeStruct((c, batch), jnp.int32), keeps, _f32(c))
