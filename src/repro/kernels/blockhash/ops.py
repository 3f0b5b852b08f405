"""Host-facing checksum API used by the kernel-services binding.

A buffer is hashed as rows of 4 KiB: the row kernel hashes each row, and
the row hashes combine as h = sum_r h_r * P^(1024 * rows after r). Zero
words in front of a buffer leave its hash unchanged, so every buffer is
right-aligned in its rows and the row count is padded at the front to a
bucket: a handful of compiled shapes serve every length. Interpret mode
runs only where the caller asks for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span, traced
from repro.kernels.blockhash import kernel as K
from repro.kernels.blockhash import ref

ROW_BYTES = 4 * K.WORDS
STEP_ROWS = 256  # rows per grid step of a long buffer: 1 MiB of VMEM


def bucket(n: int):
    """Rows a batch of ``n`` rows is padded to, and the rows per grid
    step: a power of two from 8 up to STEP_ROWS, then multiples of a step
    that keeps the padding under an eighth."""
    if n <= STEP_ROWS:
        rows = max(8, 1 << (n - 1).bit_length())
        return rows, rows
    step = max(STEP_ROWS, 1 << (n.bit_length() - 4))
    return -(-n // step) * step, STEP_ROWS


@functools.lru_cache(maxsize=None)
def _pows() -> jax.Array:
    return jnp.asarray(ref.powers(K.WORDS)[None, :])


@functools.lru_cache(maxsize=32)
def _row_mults(rows: int) -> jax.Array:
    return jnp.asarray(ref.powers(rows, pow(int(ref.PRIME), K.WORDS, 1 << 32)))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hash_rows(words, pows, *, block_rows: int, interpret: bool = False):
    """(rows, 1024) uint32 -> (rows,) uint32 row hashes."""
    return K.blockhash_batch(words, pows, block_rows=block_rows,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hash_buffer(words, pows, mults, *, block_rows: int,
                interpret: bool = False):
    """(rows, 1024) uint32 -> the uint32 hash of all rows in order."""
    h = K.blockhash_batch(words, pows, block_rows=block_rows,
                          interpret=interpret)
    as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.sum(as_i32(h) * as_i32(mults), dtype=jnp.int32), jnp.uint32)


def _place(row: np.ndarray, data) -> None:
    """Right-align ``data`` in ``row``, zero-filled to a word boundary."""
    start = row.size - 4 * (-(-len(data) // 4))
    row[start:start + len(data)] = np.frombuffer(data, np.uint8)


@traced("blockhash.buffer")
def checksum(data, *, interpret: bool = False) -> int:
    """Hash one buffer of any length (a block, a whole shard file)."""
    rows, block_rows = bucket(max(1, -(-len(data) // ROW_BYTES)))
    buf = np.zeros(rows * ROW_BYTES, np.uint8)
    _place(buf, data)
    words = buf.view(np.uint32).reshape(rows, K.WORDS)
    out = hash_buffer(words, _pows(), _row_mults(rows),
                      block_rows=block_rows, interpret=interpret)
    return int(out)


@functools.lru_cache(maxsize=None)
def _warm_bucket(rows: int, block_rows: int, interpret: bool) -> None:
    hash_rows(np.zeros((rows, K.WORDS), np.uint32), _pows(),
              block_rows=block_rows, interpret=interpret).block_until_ready()


@traced("blockhash.warm")
def warm_batch(max_n: int, *, interpret: bool = False) -> None:
    """Compile, once a process, each bucket a ``checksum_batch`` of 1 to
    ``max_n`` blocks launches."""
    for rows, block_rows in sorted({bucket(n) for n in range(1, max_n + 1)}):
        _warm_bucket(rows, block_rows, interpret)


def checksum_batch(blocks, *, interpret: bool = False) -> list:
    """Hash many buffers in one launch when each fits a row (the journal
    commit's blocks); longer buffers are hashed one by one."""
    blocks = list(blocks)
    if not blocks:
        return []
    if any(len(b) > ROW_BYTES for b in blocks):
        return [checksum(b, interpret=interpret) for b in blocks]
    with span("blockhash.batch"):
        n = len(blocks)
        rows, block_rows = bucket(n)
        buf = np.zeros((rows, ROW_BYTES), np.uint8)
        if all(len(b) == ROW_BYTES for b in blocks):
            buf[:n] = np.frombuffer(b"".join(blocks), np.uint8).reshape(
                n, ROW_BYTES)
        else:
            for row, b in zip(buf, blocks):
                _place(row, b)
        out = hash_rows(buf.view(np.uint32), _pows(), block_rows=block_rows,
                        interpret=interpret)
        return np.asarray(out)[:n].tolist()
