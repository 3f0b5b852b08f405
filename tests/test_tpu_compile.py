"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e, at the widths the chip runs: what Mosaic refuses here (block tiling,
unsigned reductions, VMEM) fails in CI instead of on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file. Nothing runs, so these tests say nothing about results or speed.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.blockhash import kernel as bh_k, ops as bh_ops
from repro.kernels.flash_attention import kernel as fa_k

SMOLLM_EMBED_BYTES = 49152 * 576 * 4  # the largest SmolLM-135M leaf, f32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# a commit of a 64-block log hashes up to 63 blocks, of the widest log
# (layout.NLOG_MAX) up to 510, in the 512-row bucket
@pytest.mark.parametrize("n", [1, 63, 64, 255, 510])
def test_blockhash_batch_compiles(shape, n):
    rows, block_rows = bh_ops.bucket(n)
    compiled = bh_ops.hash_rows.lower(
        shape((rows, bh_k.WORDS), jnp.uint32),
        shape((1, bh_k.WORDS), jnp.uint32), block_rows=block_rows).compile()
    _assert_kernel(compiled)


def test_blockhash_long_buffer_compiles(shape):
    rows, block_rows = bh_ops.bucket(-(-SMOLLM_EMBED_BYTES // bh_ops.ROW_BYTES))
    compiled = bh_ops.hash_buffer.lower(
        shape((rows, bh_k.WORDS), jnp.uint32),
        shape((1, bh_k.WORDS), jnp.uint32), shape((rows,), jnp.uint32),
        block_rows=block_rows).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles_at_smollm_widths(shape):
    """SmolLM-135M: 9 q heads, 3 kv heads, head_dim 64, bf16, S=512."""
    q = shape((8, 512, 9, 64), jnp.bfloat16)
    kv = shape((8, 512, 3, 64), jnp.bfloat16)
    fwd = jax.jit(functools.partial(fa_k.flash_attention_fwd, causal=True))
    _assert_kernel(fwd.lower(q, kv, kv).compile())
