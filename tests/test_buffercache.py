"""BufferHead finalizer-race regressions (paper §4.7 drop semantics).

The pre-fix cache had three holes around the ``__del__``/``brelse`` race:
a double release could decrement a refcount twice, a finalizer running
after ``invalidate()`` minted a NEGATIVE refs entry (which could silently
cancel a real +1 leak on the same block), and a finalizer firing during
interpreter/cache teardown sprayed "Exception ignored in __del__" noise.
These tests pin the idempotent-release protocol that closed them.

The second half covers bulk reads: a read batch whose uncached blocks
number at least the cache's capacity is gathered by one device call per
request (``BufferCache.read_into``) instead of a BufferHead per block, and
must read exactly what the per-block path reads.
"""

import collections
import functools
import gc

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.interface import Errno, FsError, SubmissionEntry
from repro.core.registry import mount as bento_mount
from repro.core.services import kernel_binding
from repro.fs import layout as L
from repro.fs.blockdev import (BlockDeviceError, FileBlockDevice,
                               LazyBlockDevice, MemBlockDevice)
from repro.fs.buffercache import BufferCache, BufferLeak
from repro.fs.ext4like import Ext4LikeFileSystem
from repro.fs.mounts import MountedFs
from repro.fs.posix import PosixView
from repro.fs.xv6 import Xv6FileSystem, Xv6Options, mkfs


def test_dropped_unreleased_head_unpins_cleanly():
    cache = BufferCache(MemBlockDevice(16))
    bh = cache.bread(4)
    bh.mark_dirty()
    del bh  # drop -> brelse, including the dirty writeback
    gc.collect()
    cache.assert_no_leaks()
    assert cache._refs == {}


def test_double_release_never_goes_negative():
    """brelse twice + the GC finalizer afterwards: exactly one unpin."""
    cache = BufferCache(MemBlockDevice(16))
    bh = cache.bread(5)
    other = cache.bread(5)  # second pin keeps the refs entry observable
    bh.brelse()
    bh.brelse()
    bh.__del__()  # the finalizer racing an explicit brelse
    assert cache._refs[5] == 1, "double release decremented twice"
    other.brelse()
    cache.assert_no_leaks()


def test_brelse_many_skips_already_released_heads():
    cache = BufferCache(MemBlockDevice(16))
    heads = cache.bread_many([1, 2, 3])
    heads[1].brelse()
    cache.brelse_many(heads)  # one head already gone — must not double-unpin
    cache.assert_no_leaks()
    assert cache._refs == {}


def test_finalizer_after_invalidate_mints_no_negative_entry():
    """A head outliving ``invalidate()`` unpins to NOTHING. Pre-fix it
    wrote refs[b] = -1, which a later un-released bread of the same block
    would cancel back to 0 — masking a real leak from the detector."""
    cache = BufferCache(MemBlockDevice(16))
    stale = cache.bread(7)
    cache.invalidate()  # drops the refs table wholesale
    del stale  # finalizer fires with no refs entry behind it
    gc.collect()
    assert 7 not in cache._refs
    leaked = cache.bread(7)  # new pin, never released
    with pytest.raises(BufferLeak, match="7"):
        cache.assert_no_leaks()
    leaked.brelse()
    cache.assert_no_leaks()


def test_finalizer_survives_cache_teardown():
    """__del__ during interpreter shutdown can find the cache (or its
    lock) already torn down; it must swallow, not spray 'Exception
    ignored' noise."""
    cache = BufferCache(MemBlockDevice(16))
    bh = cache.bread(8)

    def boom(_bh):
        raise RuntimeError("lock is gone")

    cache._release = boom
    bh.__del__()  # must not raise
    assert bh._held  # the unpin genuinely did not happen
    del cache._release  # restore the real method
    bh.brelse()
    cache.assert_no_leaks()


def test_bread_many_failure_strands_no_pins():
    """All-or-nothing bulk read: when the device run fails, the warm
    prefix already pinned must unpin before the error propagates."""
    dev = MemBlockDevice(16)
    cache = BufferCache(dev)
    cache.bread(0).brelse()  # warm one block

    def fail(_blocknos):
        raise IOError("device gone")

    dev.read_many = fail
    with pytest.raises(IOError, match="device gone"):
        cache.bread_many([0, 1, 2])
    cache.assert_no_leaks()
    assert cache._refs == {}


def test_bulk_read_past_capacity_is_one_pass():
    """A bread_many larger than the cache pins every block it returns, so
    the cache grows past capacity. That takes one eviction pass, not one
    pass over every pinned block per inserted block (a 27,648-block shard
    read was quadratic), and the cache shrinks back once the heads go."""
    n, cap = 5000, 64

    class CountingDict(collections.OrderedDict):
        moves = 0

        def move_to_end(self, key, last=True):
            CountingDict.moves += 1
            super().move_to_end(key, last)

    dev = MemBlockDevice(n + 1)
    for b in range(n):
        dev.write_block(b, bytes([b % 251]) * dev.block_size)
    cache = BufferCache(dev, capacity=cap)
    cache._blocks = CountingDict()
    for b in range(cap):  # a full cache of unpinned blocks to evict first
        cache.bread(b).brelse()
    heads = cache.bread_many(list(range(n)))
    assert [h.data()[0] for h in heads] == [b % 251 for b in range(n)]
    assert len(cache._blocks) == n
    assert CountingDict.moves < 3 * n
    cache.brelse_many(heads)
    cache.bread(n).brelse()
    assert len(cache._blocks) == cap
    cache.assert_no_leaks()


def test_bread_many_keeps_a_cached_block_its_own_misses_evict():
    """A run longer than the cache whose cached block sits behind its
    misses: inserting the misses evicts that block before its head is
    taken, and the run still returns it (it raised KeyError)."""
    dev = MemBlockDevice(64)
    for b in range(64):
        dev.write_block(b, bytes([b]) * dev.block_size)
    cache = BufferCache(dev, capacity=4)
    cache.bread(40).brelse()
    heads = cache.bread_many([1, 2, 3, 4, 5, 6, 40])
    assert [h.data()[0] for h in heads] == [1, 2, 3, 4, 5, 6, 40]
    assert (cache.hits, cache.misses) == (1, 7)
    cache.brelse_many(heads)
    cache.assert_no_leaks()


# --- bulk reads past the cache's capacity -----------------------------------

BS = L.BSIZE
L1_END = L.NDIRECT + L.NINDIRECT  # first logical block of the L2 range
SMALL, LARGE = 16, 1 << 20  # cache capacities: bulk path forced / never


def _mount(dev, kind, cap, *, fresh=False, dedup=False) -> MountedFs:
    ks = kernel_binding(dev, cache_capacity=cap)
    if fresh:
        mkfs(ks)
    cls = Ext4LikeFileSystem if kind == "ext4like" else Xv6FileSystem
    fs = cls(Xv6Options(group_commit=True, batched_install=True,
                        dedup=dedup))
    m = bento_mount(kind, ks, module=fs)
    return MountedFs(kind, m, PosixView(m), ks, dev)


@functools.lru_cache(maxsize=None)
def _image(kind):
    """A device holding ``/big`` (direct, L1 and L2 blocks, an unaligned
    tail), ``/holes`` (four mapped blocks between unmapped ones and a
    hole at the tail) and a directory ``/d``; with their contents."""
    big = np.random.default_rng(7).integers(
        0, 256, (L1_END + 40) * BS - 1234, np.uint8).tobytes()
    holes = bytearray((L1_END + 8) * BS)
    dev = MemBlockDevice(8192)
    mf = _mount(dev, kind, LARGE, fresh=True)
    v = mf.view
    v.write_file("/big", big)
    for bn, fill in ((0, 1), (5, 2), (L.NDIRECT + 3, 3), (L1_END + 2, 4)):
        chunk = bytes([fill]) * 1000
        v.write_file("/holes", chunk, off=bn * BS + 7)
        holes[bn * BS + 7: bn * BS + 1007] = chunk
    v.truncate("/holes", len(holes))
    v.makedirs("/d")
    mf.close()
    return dev, {"/big": big, "/holes": bytes(holes)}


def _spy_bulk(mf):
    """Count the mount's ``sb_bread_bulk`` calls."""
    calls = []
    real = mf.services.sb_bread_bulk

    def spy(sb, blocknos, out):
        calls.append(len(blocknos))
        return real(sb, blocknos, out)

    mf.services.sb_bread_bulk = spy
    return calls


def _pend(mf, model):
    """Rewrite pieces of /big in every addressing range, left pending in
    the journal's open transaction."""
    ino = mf.view.stat("/big").ino
    big = bytearray(model["/big"])
    for off, n, fill in ((3 * BS + 5, 2 * BS, 0xA1), (600 * BS, BS, 0xA2),
                         ((L1_END + 1) * BS - 9, 30, 0xA3)):
        mf.mount.call("write", ino, off, bytes([fill]) * n)
        big[off: off + n] = bytes([fill]) * n
    model["/big"] = bytes(big)
    assert mf.mount.module.journal.pending_snapshot()


def _dirty(mf, model):
    """Leave blocks of /big in every addressing range dirty in the cache
    and newer than the device, as a journal install stages them before
    its writeback."""
    fs, ks = mf.mount.module, mf.services
    di = fs._iget(mf.view.stat("/big").ino)
    big = bytearray(model["/big"])
    for bn, fill in ((4, 0xB1), (600, 0xB2), (L1_END + 1, 0xB3)):
        b = fs._bmap_ro(di, bn, {})
        with ks.sb_bread(fs.sb_cap, b) as bh:
            bh.data()[:] = bytes([fill]) * BS
            bh.mark_dirty()
        big[bn * BS: (bn + 1) * BS] = bytes([fill]) * BS
        assert mf.dev.read_block(b) != bytes([fill]) * BS
    model["/big"] = bytes(big)
    assert ks.n_dirty(fs.sb_cap) == 3


def _batch(case, n_big, n_holes):
    """(path or ino key, off, size) specs, or raw args, for one case. A
    whole read of /big rides in every batch, so the batch holds more
    uncached blocks than the small cache."""
    whole = ("/big", 0, n_big)
    if case == "boundaries":
        return [whole, ("/big", (L.NDIRECT - 1) * BS + 10, 3 * BS),
                ("/big", (L1_END - 2) * BS + 1, 5 * BS),
                ("/big", L1_END * BS, 2 * BS), ("/big", 0, BS)]
    if case == "unaligned":
        rng = np.random.default_rng(3)
        return [whole] + [("/big", int(rng.integers(0, n_big)),
                           int(rng.integers(1, 40 * BS))) for _ in range(8)]
    if case == "holes":
        return [whole, ("/holes", 0, n_holes), ("/holes", 4 * BS + 100,
                                                3 * BS),
                ("/holes", (L1_END + 2) * BS, 6 * BS)]
    if case == "past_eof":
        return [("/big", 0, 2 * n_big), ("/big", n_big - 10, 100),
                ("/big", n_big, 5), ("/big", n_big + BS, 5),
                ("/holes", 0, 0), ("/holes", n_holes - 1, BS)]
    if case in ("pending", "dirty"):
        return [whole, ("/big", 2 * BS, 4 * BS),
                ("/big", 599 * BS + 7, 3 * BS), ("/big", L1_END * BS, 2 * BS)]
    assert case == "errors"
    return [("/d", 0, 10), whole, ("/big", 0.5, 3), ("/big",),
            ("/big", -1, 10), (999999, 0, 10), ("/big", "0", 1),
            ("/d", 5, 1), ("/big", 5, 10)]


def _read(mf, specs):
    v = mf.view
    entries = []
    for i, spec in enumerate(specs):
        key = spec[0]
        ino = v.stat(key).ino if isinstance(key, str) else key
        entries.append(SubmissionEntry("read", (ino,) + tuple(spec[1:]),
                                       user_data=i))
    return mf.mount.submit(entries)


@pytest.mark.parametrize("kind", ["bento", "ext4like"])
@pytest.mark.parametrize("case", ["boundaries", "unaligned", "holes",
                                  "past_eof", "pending", "dirty", "errors"])
def test_bulk_read_equals_per_block_read(kind, case):
    """The same batch read with the bulk path forced (a small cache) and
    not (a cache larger than the batch): byte-identical results and the
    same errno in every slot, both equal to the files' contents."""
    dev, files = _image(kind)
    got = {}
    for cap in (SMALL, LARGE):
        mf = _mount(dev.snapshot(), kind, cap)
        model = dict(files)
        calls = _spy_bulk(mf)
        try:
            if case == "pending":
                _pend(mf, model)
            elif case == "dirty":
                _dirty(mf, model)
            specs = _batch(case, len(model["/big"]), len(model["/holes"]))
            comps = _read(mf, specs)
        finally:
            mf.close()  # assert_no_leaks
        assert bool(calls) == (cap == SMALL), (cap, calls)
        got[cap] = [(c.errno, None if c.result is None else bytes(c.result))
                    for c in comps]
        for spec, c in zip(specs, comps):
            if c.errno is None:
                path, off, size = spec
                assert bytes(c.result) == model[path][off: off + size], spec
    assert got[SMALL] == got[LARGE]
    if case == "errors":
        assert [e for e, _ in got[SMALL]] == [
            Errno.EISDIR, None, Errno.EINVAL, Errno.EINVAL, Errno.EINVAL,
            Errno.ESTALE, Errno.EINVAL, Errno.EISDIR, None]


@pytest.fixture
def profile(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return lambda: jax.profiler.trace(str(tmp_path), profiler_options=opts)


@pytest.mark.parametrize("kind", ["bento", "ext4like"])
def test_capacity_decides_the_read_path(kind, profile):
    """A batch below the cache's capacity takes the per-block path (hits
    and misses counted, no bulk blocks); one above it is gathered in bulk,
    counted as ``cache.bulk_blocks``, and leaves the cache no larger than
    its capacity and every ref released."""
    dev, files = _image(kind)
    cap = 256
    mf = _mount(dev.snapshot(), kind, cap)
    cache = mf.services._cache
    v = mf.view
    try:
        with profile():
            h0, m0 = cache.hits, cache.misses
            small = v.read_many([("/big", 0, 100 * BS)])
            assert cache.hits + cache.misses - h0 - m0 >= 100
            table = spans.snapshot()["counters"]
            assert table.get("cache.bulk_blocks", 0) == 0
            assert table["cache.bread_many_blocks"] >= 100
            h0, m0 = cache.hits, cache.misses
            whole = v.read_many([("/big", 0, len(files["/big"]))])
            # only the indirect blocks went through buffer heads
            assert cache.hits + cache.misses - h0 - m0 <= 3
        table = spans.snapshot()["counters"]
        assert table["cache.bulk_blocks"] == L1_END + 40
        assert small[0] == files["/big"][:100 * BS]
        assert whole[0] == files["/big"]
        assert len(cache._blocks) <= cap
        cache.assert_no_leaks()
    finally:
        mf.close()


def test_dedup_mount_keeps_its_checksum_mismatch_eio():
    """A dedup mount verifies every fetched buffer, so it keeps the
    per-block path past capacity and a torn block still reads EIO."""
    dev = MemBlockDevice(8192)
    mf = _mount(dev, "bento", SMALL, fresh=True, dedup=True)
    calls = _spy_bulk(mf)
    try:
        v, fs = mf.view, mf.mount.module
        data = np.random.default_rng(5).integers(
            0, 256, 64 * BS, np.uint8).tobytes()
        v.write_many([("/f", 0, data)], create=True, fsync=True)
        b = fs._bmap_ro(fs._iget(v.stat("/f").ino), 9, {})
        assert b in fs._blockstore.hashval
        orig = dev.read_block(b)
        dev.write_block(b, b"torn" + orig[4:])
        mf.services.sb_invalidate_blocks(fs.sb_cap, [b])
        got = _read(mf, [("/f", 0, len(data)), ("/f", 0, 8 * BS)])
        assert got[0].errno == Errno.EIO
        assert got[1].result == data[:8 * BS]
        assert calls == []
        dev.write_block(b, orig)
        mf.services.sb_invalidate_blocks(fs.sb_cap, [b])
        assert v.read_many([("/f", 0, len(data))]) == [data]
    finally:
        mf.close()


@pytest.mark.parametrize("backend", ["mem", "file"])
def test_read_many_into_equals_read_many_and_counts_reads(backend,
                                                          tmp_path):
    dev = (MemBlockDevice(64) if backend == "mem"
           else FileBlockDevice(str(tmp_path / "dev"), 64))
    rng = np.random.default_rng(1)
    for b in range(64):
        dev.write_block(b, rng.integers(0, 256, BS, np.uint8).tobytes())
    blocks = [5, 0, 63, 5, 17]
    out = np.empty((len(blocks), BS), np.uint8)
    r0 = dev.reads
    dev.read_many_into(np.array(blocks), out)
    assert dev.reads - r0 == len(blocks)
    assert [r.tobytes() for r in out] == dev.read_many(blocks)
    with pytest.raises(BlockDeviceError, match="out of range"):
        dev.read_many_into([3, 64], np.empty((2, BS), np.uint8))


def test_cache_read_into_serves_cached_and_dirty_blocks_and_keeps_none():
    """Cached blocks come from the cache (a dirty one is newer than the
    device), the rest from one device call; nothing is inserted, pinned
    or counted."""
    dev = MemBlockDevice(16)
    for b in range(16):
        dev.write_block(b, bytes([b]) * BS)
    cache = BufferCache(dev, capacity=4, writeback="delayed")
    with cache.bread(3) as bh:
        bh.data()[:] = b"\x77" * BS
        bh.mark_dirty()
    calls = []
    real = dev.read_many_into
    dev.read_many_into = lambda bs, out: (calls.append(list(bs)),
                                          real(bs, out))
    out = np.empty((4, BS), np.uint8)
    hits, misses, cached = cache.hits, cache.misses, list(cache._blocks)
    cache.read_into([9, 3, 1, 9], out)
    assert [r[0] for r in out] == [9, 0x77, 1, 9]
    assert calls == [[9, 1, 9]]
    assert (cache.hits, cache.misses) == (hits, misses)
    assert list(cache._blocks) == cached and cache._refs == {}
    assert dev.read_block(3)[0] == 3  # the dirty block is still unwritten


def test_lazy_device_under_bulk_path_keeps_round_trips_and_protocol():
    """A lazy device behind the bulk path: one provider round trip for a
    request's misses, the same as the per-block path, and a power cut
    during materialization leaves no torn block visible."""
    image, files = _image("bento")
    big = files["/big"]
    trips = {}
    for cap in (SMALL, LARGE):
        lazy = LazyBlockDevice(image, n_blocks=image.n_blocks)
        mf = _mount(lazy, "bento", cap)
        calls = _spy_bulk(mf)
        try:
            mf.view.read_many([("/big", 0, BS)])  # the inode and L1
            t0, f0 = lazy.provider_round_trips, lazy.provider_blocks_fetched
            assert mf.view.read_many([("/big", 0, len(big))]) == [big]
            trips[cap] = (lazy.provider_round_trips - t0,
                          lazy.provider_blocks_fetched - f0)
        finally:
            mf.close()
        assert bool(calls) == (cap == SMALL)
    assert trips[SMALL] == trips[LARGE]

    lazy = LazyBlockDevice(image, n_blocks=image.n_blocks)
    mf = _mount(lazy, "bento", SMALL)
    try:
        mf.view.read_many([("/big", 0, BS)])
        lazy.fail_after_writes = lazy._writes_seen + 301  # mid-run
        lazy.fail_torn_bytes = 100
        got = mf.view.read_many([("/big", 0, len(big))], strict=False)
        assert isinstance(got[0], FsError) and got[0].errno == Errno.EIO
        valid = np.flatnonzero(lazy._valid)
        assert (lazy._data[valid] == image._data[valid]).all()
        fs = mf.mount.module
        rows = fs._bmap_rows(fs._iget(mf.view.stat("/big").ino), 0,
                             len(big) // BS + 1, {})
        assert 0 < lazy._valid[rows].sum() < rows.size
        lazy.fail_after_writes = -1
        assert mf.view.read_many([("/big", 0, len(big))]) == [big]
    finally:
        mf.close()
