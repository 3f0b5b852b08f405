"""Block-checksum Pallas kernel.

Rows of 1024 u32 words (one 4 KiB block each) arrive as an ``(n, 1024)``
array, ``n`` a multiple of 8, so eight blocks fill the sublanes of each
``(8, 128)`` tile. One grid step multiplies ``block_rows`` rows by the
power vector on the VPU and folds the eight 128-lane slices of each row
together, which needs no relayout. The kernel's output is lane-dense,
``(n, 128)`` partial sums, which XLA then sums over lanes. The arithmetic
is int32, which wraps exactly like u32 (Mosaic has no unsigned
reductions); the result is bitcast back to uint32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
WORDS = 1024  # u32 words per 4 KiB block: one row


def _kernel(words_ref, pows_ref, out_ref):
    x = words_ref[...] * pows_ref[...]  # (block_rows, 1024), wraps
    acc = x[:, :LANES]
    for j in range(1, WORDS // LANES):
        acc = acc + x[:, j * LANES:(j + 1) * LANES]
    out_ref[...] = acc


def blockhash_batch(words: jax.Array, pows: jax.Array, *, block_rows: int,
                    interpret: bool = False) -> jax.Array:
    """words: (n, 1024) uint32 with ``n % block_rows == 0``; pows: (1, 1024)
    uint32 -> (n,) uint32, the hash of each row."""
    n, wpb = words.shape
    assert wpb == WORDS and block_rows % 8 == 0 and n % block_rows == 0, (
        words.shape, block_rows)
    partial = pl.pallas_call(
        _kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, WORDS), lambda i: (i, 0)),
            pl.BlockSpec((1, WORDS), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="blockhash",
    )(jax.lax.bitcast_convert_type(words, jnp.int32),
      jax.lax.bitcast_convert_type(pows, jnp.int32))
    return jax.lax.bitcast_convert_type(
        jnp.sum(partial, axis=1, dtype=jnp.int32), jnp.uint32)
