"""Crash-recovery tests: for workloads with a crash injected at an
arbitrary device-write count, journal recovery must yield a consistent
file system in which every fsync'd file is intact — recovered content must
be the fsync'd version or a *later committed* version (group commit may
durably commit subsequent writes on its own). Chained submissions add a
stronger unit: a chain is ONE journal transaction (chain-aware
reservation), so it is crash-atomic at every device-write point.

Crash injection, remount-cold recovery and crash-point enumeration all
live in the shared harness (``repro.fs.crashsim``) — this file carries
the randomized-workload property (hypothesis, when available) and the
deterministic journal unit tests; the exhaustive sweeps are in
``tests/test_crash_torture.py``.
"""

import pytest

try:
    import hypothesis as hp
    import hypothesis.strategies as st
except ImportError:  # deterministic tests still run
    hp = None
    st = None

from repro.core.interface import Errno
from repro.core.services import kernel_binding
from repro.fs.blockdev import MemBlockDevice
from repro.fs.crashsim import CrashSim, all_or_nothing, chain_workload
from repro.fs.posix import PosixView
from repro.fs.xv6 import Xv6FileSystem, Xv6Options, mkfs
from repro.fs.mounts import DirectMount


def _fresh_fs(dev=None, n_blocks=2048):
    dev = dev or MemBlockDevice(n_blocks)
    ks = kernel_binding(dev, writeback="delayed")
    if dev.writes == 0:
        mkfs(ks, ninodes=256, nlog=32)
    fs = Xv6FileSystem(Xv6Options(group_commit=True, batched_install=True))
    fs.init(ks.superblock(), ks)
    return dev, ks, fs, PosixView(DirectMount(fs))


def _sim() -> CrashSim:
    return CrashSim(
        lambda: Xv6FileSystem(Xv6Options(group_commit=True,
                                         batched_install=True)))


if hp is not None:
    ops_strategy = st.lists(
        st.tuples(
            st.sampled_from(["write", "append", "fsync_file", "delete"]),
            st.integers(0, 5),          # file index
            st.integers(1, 3),          # payload blocks
        ),
        min_size=1, max_size=25,
    )

    @hp.given(ops=ops_strategy, crash_after=st.integers(1, 400),
              data_seed=st.integers(0, 2**16))
    @hp.settings(max_examples=30, deadline=None)
    def test_crash_recovery_preserves_fsynced_data(ops, crash_after,
                                                   data_seed):
        _crash_recovery_body(ops, crash_after, data_seed)


def _crash_recovery_body(ops, crash_after, data_seed):
    """One randomized workload at one crash point, on the shared harness:
    the workload mutates the model dicts as it goes; the asserts read them
    against the recovered view."""
    history = {}   # path -> list of every version ever written
    floor = {}     # path -> index into history guaranteed durable (fsync)

    def payload(i, blocks):
        return bytes([(data_seed + i) % 251]) * (blocks * 4096)

    def workload(ctx):
        v = ctx.view
        for i, (op, fidx, blocks) in enumerate(ops):
            path = f"/f{fidx}"
            if op == "write":
                data = payload(i, blocks)
                v.write_file(path, data)
                hist = history.setdefault(path, [])
                # write_file overwrites from offset 0; tail of a longer
                # older version survives -> compute effective content
                prev = hist[-1] if hist else b""
                hist.append(data + prev[len(data):])
            elif op == "append":
                data = payload(i, blocks)
                hist = history.setdefault(path, [b""])
                v.append(path, data)
                hist.append(hist[-1] + data)
            elif op == "fsync_file":
                if path in history:
                    v.fsync(path)
                    floor[path] = len(history[path]) - 1
            elif op == "delete":
                if path in history and v.exists(path):
                    v.unlink(path)
                    history.pop(path)
                    floor.pop(path, None)
        # reached only when no crash fired inside the loop: disarm the
        # injector (like the original hand-rolled test — power stays on)
        # and drain to disk, so EVERY surviving version must be durable
        ctx.dev.fail_after_writes = -1
        ctx.fs.flush()
        for p in history:
            floor[p] = len(history[p]) - 1

    rec = _sim().run_one(workload, crash_after)
    v2 = rec.view
    for path, fl in floor.items():
        if path not in history:
            continue  # deleted later; no durability claim on deletes
        assert v2.exists(path), f"{path} was fsync'd but lost after crash"
        got = v2.read_file(path)
        acceptable = history[path][fl:]
        assert any(got == h for h in acceptable), (
            f"{path}: recovered {len(got)}B matches no committed version at "
            f"or after the fsync point")
    # general consistency
    v2.statfs()
    v2.listdir("/")


def test_torn_journal_commit_discarded():
    """Corrupt one journal data block after a staged commit record: recovery
    must detect the checksum mismatch and discard (no partial replay)."""
    from repro.fs.layout import pack_log_record

    dev, ks, fs, v = _fresh_fs()
    v.write_file("/a", b"A" * 4096)
    fs.journal.commit()
    geo = fs.geo
    bogus = b"\x42" * 4096
    dev.write_block(geo.logstart, pack_log_record(
        99, [(geo.datastart + 5, ks.checksum(bogus))]))
    dev.write_block(geo.logstart + 1, b"TORN" * 1024)  # checksum mismatch
    fs2 = Xv6FileSystem(Xv6Options())
    ks2 = kernel_binding(dev)
    fs2.init(ks2.superblock(), ks2)
    assert fs2.journal.recover() == 0  # discarded, no replay


def test_journal_absorption():
    dev, ks, fs, v = _fresh_fs()
    ino = v.create("/f").ino
    for _ in range(10):
        fs.write(ino, 0, b"same block" * 10)
    assert len(fs.journal._pending) < 8
    fs.journal.commit()
    assert fs.journal.pending_get(0) is None


def test_commit_refused_mid_chain_and_run_by_end_chain():
    """The reservation contract at the journal level: commits requested
    while a chain scope is open defer to end_chain — the chain's blocks
    become durable in ONE transaction, never two."""
    dev, ks, fs, v = _fresh_fs()
    j = fs.journal
    c0 = j.commits
    j.begin_chain(8)
    assert j.in_chain
    j.log_write(fs.geo.datastart + 1, b"a" * 4096)
    j.commit()                       # refused: deferred, nothing written
    assert j.commits == c0 and j._pending
    j.log_write(fs.geo.datastart + 2, b"b" * 4096)
    j.end_chain()                    # deferred commit runs here, once
    assert j.commits == c0 + 1 and not j._pending and not j.in_chain


def test_crash_mid_chain_never_half_applied():
    """The PR 2 hand-rolled sweep, ported onto the shared harness: a
    chained create→write(PrevResult)→fsync crashed at EVERY device-write
    point recovers all-or-nothing (the chain now holds as one journal
    transaction by construction, not by luck of group-commit sizing)."""
    payload = b"C" * (2 * 4096 + 17)  # multi-block: a torn chain would show
    points = _sim().sweep(chain_workload(payload), all_or_nothing(payload))
    assert points > 4  # create+write+commit really hit the device


# --- torn writes vs verified reads (the BlockStore integrity tripwire) -----------
#
# Dedup mounts hash every flushed data block; bulk reads re-hash what the
# cache fetched and surface mismatches as EIO. These sweeps tear ONE
# tracked device block at a time behind the cache's back and assert the
# detector is exact: EIO for precisely the reads that touch the torn
# block, byte-identical data everywhere else, and clean reads again once
# the block's true content is restored.


def _torn_corpus(kind):
    """A small dup-heavy corpus on a fresh dedup mount: 6 files x 4
    blocks from a 6-block pool (shared AND unique blocks end up tracked).
    Returns (mf, files, block_files) where block_files maps device block
    -> set of paths referencing it."""
    from repro.fs.mounts import make_mount

    mf = make_mount(kind, n_blocks=4096)
    v, fs = mf.view, mf.mount.module
    pool = [bytes([17 * (i + 1) % 251]) * 4096 for i in range(6)]
    files = {f"/t{i}": pool[i % 6] + pool[(i + 1) % 6] + pool[0] + pool[i % 3]
             for i in range(6)}
    v.write_many([(p, 0, d) for p, d in files.items()], create=True,
                 fsync=True)
    block_files = {}
    for p in files:
        di = fs._iget(v._walk(p))
        cache = {}
        for bn in range((di.size + 4095) // 4096):
            block_files.setdefault(fs._bmap_ro(di, bn, cache), set()).add(p)
    return mf, files, block_files


def _tear(mf, b, payload=b"torn-behind-the-cache!"):
    """Corrupt device block b under the cache and drop the cached copy;
    returns the original bytes for later restore."""
    orig = bytes(mf.dev.read_block(b))
    raw = bytearray(orig)
    raw[:len(payload)] = payload
    mf.dev.write_block(b, bytes(raw))
    fs = mf.mount.module
    mf.services.sb_invalidate_blocks(fs.sb_cap, [b])
    return orig


@pytest.mark.parametrize("kind", ["dedup-bento", "dedup-ext4like"])
def test_torn_block_sweep_verified_read_many_exact(kind):
    """Sweep EVERY tracked block: tear it, bulk-read the corpus with
    strict=False — EIO lands on exactly the files that reference the torn
    block (shared blocks poison every sharer), clean files stay
    byte-identical, the corruption counter ticks, and restoring the true
    bytes makes the whole corpus read clean again."""
    from repro.core.interface import FsError

    mf, files, block_files = _torn_corpus(kind)
    try:
        v, fs = mf.view, mf.mount.module
        store = fs._blockstore
        tracked = sorted(store.hashval)
        assert len(tracked) >= 4  # the corpus really left hashed blocks
        paths = sorted(files)
        for b in tracked:
            expect_bad = block_files.get(b, set())
            assert expect_bad, f"tracked block {b} not referenced by corpus"
            c0 = v.statfs()["dedup_corruptions_detected"]
            orig = _tear(mf, b)
            got = v.read_many(paths, strict=False)
            bad = {p for p, r in zip(paths, got) if isinstance(r, FsError)}
            assert bad == expect_bad, \
                f"block {b}: EIO on {bad}, expected {expect_bad}"
            for p, r in zip(paths, got):
                if p in expect_bad:
                    assert r.errno == Errno.EIO
                else:
                    assert r == files[p], f"{p} dirtied by unrelated tear"
            assert v.statfs()["dedup_corruptions_detected"] > c0
            # restore the true content: verification must pass again
            mf.dev.write_block(b, orig)
            mf.services.sb_invalidate_blocks(fs.sb_cap, [b])
            clean = v.read_many(paths, strict=False)
            assert [r for r in clean if isinstance(r, FsError)] == []
            assert all(r == files[p] for p, r in zip(paths, clean))
    finally:
        mf.close()


@pytest.mark.parametrize("kind", ["dedup-bento", "dedup-ext4like"])
def test_torn_block_slice_reads_are_block_precise(kind):
    """Detection is per fetched block, not per file: a ranged read_many
    slice that avoids the torn block succeeds even inside a file whose
    OTHER blocks are torn, while any slice overlapping it gets EIO."""
    from repro.core.interface import FsError

    mf, files, block_files = _torn_corpus(kind)
    try:
        v, fs = mf.view, mf.mount.module
        # pick a block referenced mid-file so both sides exist
        victim_path, victim_bn = None, None
        for p in sorted(files):
            di = fs._iget(v._walk(p))
            b1 = fs._bmap_ro(di, 1, {})
            if b1 in fs._blockstore.hashval:
                victim_path, victim_bn, victim_b = p, 1, b1
                break
        assert victim_path is not None
        _tear(mf, victim_b)
        specs = [(victim_path, 0, 4096),              # before the tear
                 (victim_path, victim_bn * 4096, 4096),   # the torn block
                 (victim_path, 2 * 4096, 4096)]       # after the tear
        got = v.read_many(specs, strict=False)
        data = files[victim_path]
        sharers = block_files[victim_b]
        assert isinstance(got[1], FsError) and got[1].errno == Errno.EIO
        if victim_b not in (fs._bmap_ro(fs._iget(v._walk(victim_path)), 0, {}),
                            fs._bmap_ro(fs._iget(v._walk(victim_path)), 2, {})):
            assert got[0] == data[:4096]
            assert got[2] == data[2 * 4096:3 * 4096]
        # strict=True raises out of the batch instead of returning slots
        with pytest.raises(FsError):
            v.read_many([(victim_path, victim_bn * 4096, 4096)])
        # every OTHER sharer of the shared torn block is poisoned too
        others = sorted(sharers - {victim_path})
        if others:
            got2 = v.read_many(others, strict=False)
            assert all(isinstance(r, FsError) for r in got2)
    finally:
        mf.close()
