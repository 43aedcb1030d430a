"""The main path's Pallas kernels compile for a TPU v5e chip, at real
widths.

Nothing runs: each test lowers a kernel wrapper for a described (not
attached) v5e device and compiles it with the TPU compiler, which
refuses what interpret mode accepts (unaligned tiles, too much VMEM).
The topology is described inside a fixture of this file, never at
import: only one process may load the TPU library, and every test
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("gpt3_0_7b")
EMBED = (CFG.vocab_size, CFG.d_model)         # the largest leaf, 50257x1536
DIRTY_BLOCK_BYTES = 4096                      # FastPersistConfig.dirty_block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ckpt_pack_f32_to_bf16_compiles(one_chip):
    x = jax.ShapeDtypeStruct(EMBED, jnp.float32, sharding=one_chip)
    _compile(lambda x: ops.ckpt_pack(x, block=4096), x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ckpt_pack_dirty_compiles(one_chip, dtype):
    block = DIRTY_BLOCK_BYTES // jnp.dtype(dtype).itemsize
    x = jax.ShapeDtypeStruct(EMBED, dtype, sharding=one_chip)
    img = jax.eval_shape(lambda x: ops.pack_blocks(x, block=block),
                         jax.ShapeDtypeStruct(EMBED, dtype))
    prev = jax.ShapeDtypeStruct(img.shape, img.dtype, sharding=one_chip)
    compiled = _compile(
        lambda x, p: ops.ckpt_pack_dirty(x, p, block=block), x, prev)
    # one (8,128) f32 / (16,128) bf16 tile per mask block
    assert img.shape[1:] == (block // 128, 128)
    assert compiled.memory_analysis() is not None


def test_flash_attention_compiles(one_chip):
    hd = CFG.resolved_head_dim
    q = jax.ShapeDtypeStruct((2, CFG.n_heads, 1024, hd), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v), q, q, q)
