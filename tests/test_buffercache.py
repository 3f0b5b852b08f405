"""BufferHead finalizer-race regressions (paper §4.7 drop semantics).

The pre-fix cache had three holes around the ``__del__``/``brelse`` race:
a double release could decrement a refcount twice, a finalizer running
after ``invalidate()`` minted a NEGATIVE refs entry (which could silently
cancel a real +1 leak on the same block), and a finalizer firing during
interpreter/cache teardown sprayed "Exception ignored in __del__" noise.
These tests pin the idempotent-release protocol that closed them.
"""

import collections
import gc

import pytest

from repro.fs.blockdev import MemBlockDevice
from repro.fs.buffercache import BufferCache, BufferLeak


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
