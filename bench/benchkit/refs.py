"""Plain references the comparison that decides ``correct`` uses. They
import nothing of the program under test.

``blockhash`` is the on-disk checksum's definition, written out on the
host: the buffer, zero-padded to whole 32-bit little-endian words
w_0..w_{n-1}, hashes to sum_i w_i * P^(n-1-i) mod 2^32 with P =
0x01000193. It runs in chunks so a 100 MB shard needs no 100 MB table of
powers.
"""

from __future__ import annotations

import hashlib
import zlib
from typing import List

import numpy as np

PRIME = 0x01000193
_CHUNK = 1 << 20  # words per chunk


def _powers(n: int) -> np.ndarray:
    """[P^(n-1), ..., P^1, P^0] mod 2^32."""
    # doubling keeps this vectorised: the ascending run [P^0..P^(k-1)]
    # times P^k gives the next k
    asc = np.ones(1, np.uint32)
    while asc.size < n:
        asc = np.concatenate([asc, asc * np.uint32(pow(PRIME, asc.size,
                                                       1 << 32))])
    return asc[:n][::-1].copy()


_POW_CHUNK = None


def blockhash(data) -> int:
    global _POW_CHUNK
    buf = memoryview(data).cast("B")
    pad = (-len(buf)) % 4
    if pad:
        buf = memoryview(bytes(buf) + b"\0" * pad)
    words = np.frombuffer(buf, dtype="<u4")
    if _POW_CHUNK is None:
        _POW_CHUNK = _powers(_CHUNK)
    h = np.uint32(0)
    with np.errstate(over="ignore"):
        for lo in range(0, words.size, _CHUNK):
            w = words[lo:lo + _CHUNK]
            p = _POW_CHUNK if w.size == _CHUNK else _powers(w.size)
            shift = np.uint32(pow(PRIME, w.size, 1 << 32))
            h = np.uint32(h * shift) + np.sum(w * p, dtype=np.uint32)
    return int(np.uint32(h))


def crc32(data) -> int:
    """The host binding's checksum: zlib's CRC-32."""
    return zlib.crc32(data) & 0xFFFFFFFF


def leaf_digests(tree_leaves: List) -> List[str]:
    """One digest per leaf over its dtype, shape and bytes: what a client
    saved, to hold against what it got back."""
    out = []
    for a in tree_leaves:
        a = np.ascontiguousarray(np.asarray(a))
        h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.reshape(-1).view(np.uint8))
        out.append(h.hexdigest())
    return out
