"""Host reference for the block-checksum kernel.

Polynomial hash over u32 words: h = sum_i word_i * P^(n-1-i)  (mod 2^32),
P = 0x01000193 (FNV prime). Chosen over CRC32C because CRC's bit-serial
table chaining is TPU-hostile, while a polynomial hash is a vectorizable
dot product (HW-adaptation note in DESIGN.md); collision/torn-write
detection strength is equivalent for journal-commit purposes.
"""

from __future__ import annotations

import numpy as np

PRIME = np.uint32(0x01000193)


def powers(n: int, base: int = PRIME) -> np.ndarray:
    """[base^(n-1), ..., base^1, base^0] mod 2^32 (u32 products wrap)."""
    asc = np.ones(1, dtype=np.uint32)
    while asc.size < n:  # doubling: [b^0..b^(k-1)] -> [b^0..b^(2k-1)]
        asc = np.concatenate(
            [asc, asc * np.uint32(pow(int(base), asc.size, 1 << 32))])
    return asc[:n][::-1].copy()


def blockhash_np(data: bytes) -> int:
    pad = (-len(data)) % 4
    arr = np.frombuffer(data + b"\0" * pad, dtype=np.uint32)
    p = powers(len(arr))
    return int(np.sum(arr.astype(np.uint64) * p.astype(np.uint64)) & 0xFFFFFFFF)
