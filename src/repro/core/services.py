"""Kernel Services API (BentoKS, paper §4.5–4.7).

Extensions never touch raw devices or kernel structures; they call these
methods with capability proof. Two bindings expose the SAME API (paper §4.9
— same code in kernel and userspace):

* ``kernel_binding``   — host-memory device, Pallas blockhash checksums
                         on a TPU, zlib crc32 elsewhere,
* ``userspace_binding`` — file-backed device, zlib crc32.

``KernelServices.checksum_impl`` names the hash a binding bound.

Swap the binding, not the file system.
"""

from __future__ import annotations

import functools
import threading
import time as _time
import zlib
from typing import Callable, List, Optional

from repro.core.capability import (BlockDeviceCap, CapabilityError,
                                   SuperBlockCap, mint_blockdev,
                                   mint_superblock)
from repro.core.spans import count, span
from repro.fs.blockdev import BlockDevice
from repro.fs.buffercache import BufferCache, BufferHead


class _SbState:
    """Kernel-side superblock object wrapped by SuperBlockCap."""

    def __init__(self, dev: BlockDevice, cache: BufferCache):
        self.block_size = dev.block_size
        self.n_blocks = dev.n_blocks
        self.device_id = dev.device_id
        self.cache = cache


class KernelServices:
    """What a Bento file system may do to the kernel."""

    def __init__(self, dev: BlockDevice, *, checksum: Callable[[bytes], int],
                 checksum_batch: Optional[Callable] = None,
                 warm_checksum_batch: Optional[Callable[[int], None]] = None,
                 writeback: str = "delayed", cache_capacity: int = 4096,
                 binding: str = "kernel", checksum_impl: str = "crc32"):
        self._dev = dev
        self.binding = binding
        self.checksum_impl = checksum_impl
        self._cache = BufferCache(dev, capacity=cache_capacity,
                                  writeback=writeback)
        self._sb_state = _SbState(dev, self._cache)
        self._checksum = checksum
        self._checksum_batch = checksum_batch
        self._warm_checksum_batch = warm_checksum_batch
        self._log: List[str] = []
        # Batching observability: the fs_micro --batched acceptance check
        # reads these (one checksum_batch launch per flushed batch, bulk
        # bread instead of per-block bread).
        self.counters = {"checksum_calls": 0, "checksum_batch_calls": 0,
                         "checksum_blocks": 0, "bread_many_calls": 0,
                         "bread_many_blocks": 0}
        # counter increments are read-modify-writes; concurrent read units
        # (parallel multi-submitter drain) share them
        self._counter_lock = threading.Lock()

    # --- capabilities ---------------------------------------------------------------
    def superblock(self) -> SuperBlockCap:
        return mint_superblock(self._sb_state)

    def blockdev_cap(self) -> BlockDeviceCap:
        return mint_blockdev(self._dev)

    @staticmethod
    def _cache_of(sb: SuperBlockCap) -> BufferCache:
        if not isinstance(sb, SuperBlockCap):
            raise CapabilityError("sb_bread requires a SuperBlockCap")
        return sb._raw().cache

    # --- block I/O (the sb_bread family, §4.5) -----------------------------------------
    def sb_bread(self, sb: SuperBlockCap, blockno: int) -> BufferHead:
        return self._cache_of(sb).bread(blockno)

    def sb_bread_many(self, sb: SuperBlockCap, blocknos,
                      fetched=None) -> List[BufferHead]:
        """Batched sb_bread: one cache pass for a whole submission batch.
        Heads come back in request order; each must still be released
        (brelse / context exit) — ownership rules are per-buffer.
        ``fetched`` collects device-fetched blocknos for verified reads."""
        blocknos = list(blocknos)
        with self._counter_lock:
            self.counters["bread_many_calls"] += 1
            self.counters["bread_many_blocks"] += len(blocknos)
        count("cache.bread_many_blocks", len(blocknos))
        return self._cache_of(sb).bread_many(blocknos, fetched=fetched)

    def sb_bread_bulk(self, sb: SuperBlockCap, blocknos, out) -> None:
        """Bulk read for a batch the cache cannot hold: fills ``out``, an
        ``(n, block_size)`` uint8 view, row by row in request order —
        cached blocks from the cache, the rest from one device call. No
        BufferHead, no ref, no cache insertion."""
        cache = self._cache_of(sb)
        count("cache.bulk_blocks", len(blocknos))
        cache.read_into(blocknos, out)

    def sb_cache_capacity(self, sb: SuperBlockCap) -> int:
        return self._cache_of(sb).capacity

    def sb_n_uncached(self, sb: SuperBlockCap, blocknos) -> int:
        """How many of the distinct ``blocknos`` the cache does not hold."""
        return self._cache_of(sb).n_uncached(blocknos)

    def sb_brelse_many(self, sb: SuperBlockCap,
                       heads: List[BufferHead]) -> None:
        """Batched brelse: release a bread_many batch's heads under one
        cache-lock acquisition instead of one per head."""
        self._cache_of(sb).brelse_many(heads)

    def sb_getblk_zero(self, sb: SuperBlockCap, blockno: int) -> BufferHead:
        return self._cache_of(sb).getblk_zero(blockno)

    def bwrite_sync(self, sb: SuperBlockCap, bh: BufferHead) -> None:
        self._cache_of(sb).write_now(bh)

    def flush(self, sb: SuperBlockCap, blocknos: Optional[List[int]] = None) -> int:
        """Batched writeback — the `writepages` analogue."""
        return self._cache_of(sb).flush(blocknos)

    def n_dirty(self, sb: SuperBlockCap) -> int:
        return self._cache_of(sb).n_dirty

    def sb_invalidate_blocks(self, sb: SuperBlockCap, blocknos) -> None:
        """Drop specific cached blocks (no writeback) so the next read
        refetches the device — the journal's chain-member rollback path."""
        self._cache_of(sb).invalidate_blocks(blocknos)

    # --- misc services -----------------------------------------------------------------
    def create_lock(self) -> threading.RLock:
        return threading.RLock()

    def checksum(self, data: bytes) -> int:
        with self._counter_lock:
            self.counters["checksum_calls"] += 1
        return self._checksum(data)

    def checksum_batch(self, blocks) -> List[int]:
        """Checksum many blocks in one call — the journal commit path uses
        this so the Pallas kernel launches once per transaction, not once
        per block."""
        blocks = list(blocks)
        with self._counter_lock:
            self.counters["checksum_batch_calls"] += 1
            self.counters["checksum_blocks"] += len(blocks)
        if self._checksum_batch is not None:
            return self._checksum_batch(blocks)
        return [self._checksum(b) for b in blocks]

    def warm_checksum_batch(self, max_blocks: int) -> None:
        """Compile every shape a ``checksum_batch`` of up to ``max_blocks``
        blocks launches, where the binding's hash is a compiled kernel:
        the journal calls this when it is bound, so no commit compiles."""
        if self._warm_checksum_batch is not None:
            self._warm_checksum_batch(max_blocks)

    def time(self) -> float:
        return _time.time()

    def log_warn(self, msg: str) -> None:
        self._log.append(msg)

    # --- teardown ----------------------------------------------------------------------
    def unmount_checks(self) -> None:
        self._cache.flush()
        self._cache.assert_no_leaks()


def _crc32_zlib(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF


def kernel_binding(dev: BlockDevice, **kw) -> KernelServices:
    """Kernel-mode services. On a TPU every checksum goes through the
    Pallas blockhash kernel, probed against its host reference at bind
    time; a probe that fails raises, it never falls back. Elsewhere the
    host crc32 is bound, unless REPRO_FORCE_PALLAS_CHECKSUM=1 asks for the
    kernel in interpret mode (slow, but real launches)."""
    import os

    import jax

    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or os.environ.get("REPRO_FORCE_PALLAS_CHECKSUM") == "1"):
        return KernelServices(dev, checksum=_crc32_zlib, binding="kernel",
                              **kw)
    from repro.kernels.blockhash import ops as bh_ops
    from repro.kernels.blockhash.ref import blockhash_np

    interpret = not on_tpu
    probe = bytes(range(256)) * 17  # spans two rows
    with span("services.probe"):
        got = [bh_ops.checksum(probe, interpret=interpret)] + \
            bh_ops.checksum_batch([probe[:4096]], interpret=interpret)
    want = [blockhash_np(probe), blockhash_np(probe[:4096])]
    if got != want:
        raise RuntimeError(
            f"blockhash kernel probe returned {got}, reference {want}")
    return KernelServices(
        dev, checksum=functools.partial(bh_ops.checksum, interpret=interpret),
        checksum_batch=functools.partial(bh_ops.checksum_batch,
                                         interpret=interpret),
        warm_checksum_batch=functools.partial(bh_ops.warm_batch,
                                              interpret=interpret),
        binding="kernel",
        checksum_impl="blockhash-interpret" if interpret else
        "blockhash-pallas", **kw)


def userspace_binding(dev: BlockDevice, **kw) -> KernelServices:
    return KernelServices(dev, checksum=_crc32_zlib, binding="userspace", **kw)
