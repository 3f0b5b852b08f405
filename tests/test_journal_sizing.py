"""The journal's size comes from the device at mkfs: a 256th of it, from
64 blocks (every device up to 16,384 blocks) up to 511, the most one
commit record can describe. These tests pin the rule and its limit, the
crash safety of a full-width (510-entry) commit, torn headers included,
how often a large write commits, that binding a journal compiles every
hash shape its commits launch, and that a device formatted with a
64-block log keeps it."""

import struct

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.registry import mount as bento_mount
from repro.core.services import KernelServices, kernel_binding
from repro.fs import crashsim
from repro.fs import layout as L
from repro.fs.blockdev import BlockDeviceError, MemBlockDevice
from repro.fs.crashsim import CrashSim, quick_points
from repro.fs.journal import Journal
from repro.kernels.blockhash import ops as bh_ops
from repro.kernels.blockhash.ref import blockhash_np
from repro.fs.mounts import DirectMount, remount
from repro.fs.posix import PosixView
from repro.fs.xv6 import MAXOP_BLOCKS, Xv6FileSystem, Xv6Options, mkfs


@pytest.fixture
def profile(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return lambda: jax.profiler.trace(str(tmp_path),
                                      profiler_options=opts)


def _payload(n_blocks: int) -> bytes:
    """Every block distinct, so a misplaced or stale block shows."""
    return np.arange(n_blocks * L.BSIZE // 4, dtype=np.uint32).tobytes()


def _bento(n_blocks: int, nlog=None):
    dev = MemBlockDevice(n_blocks)
    ks = kernel_binding(dev)
    mkfs(ks, nlog=nlog)
    fs = Xv6FileSystem(Xv6Options(group_commit=True, batched_install=True))
    return fs, PosixView(bento_mount("xv6", ks, module=fs))


@pytest.mark.parametrize("n_blocks, nlog, want", [
    (8192, None, 64),
    (16384, None, 64),        # make_mount's default: the geometry of old
    (65536, None, 256),
    (131072, None, 511),      # the Filebench varmail device
    (466600, None, 511),      # a checkpoint device for three 510 MB saves
    (16384, 511, 511),        # an explicit nlog is kept, above the rule
    (131072, 64, 64),         # and below it
    (16384, 512, ValueError),  # 511 entries do not fit one header block
    (131072, 1024, ValueError),
])
def test_log_sized_from_device(n_blocks, nlog, want):
    if want is ValueError:
        with pytest.raises(ValueError, match="commit record"):
            L.geometry(n_blocks, nlog=nlog)
        dev = MemBlockDevice(min(n_blocks, 16384))
        with pytest.raises(ValueError, match="commit record"):
            mkfs(kernel_binding(dev), nlog=nlog)
        assert dev.writes == 0  # refused before the first write
        return
    geo = L.geometry(n_blocks, nlog=nlog)
    assert geo.nlog == want and geo.inodestart == geo.logstart + want
    assert want - 1 <= L.LOG_MAX_ENTRIES
    if n_blocks <= 16384:  # the device itself, where a test can hold it
        fs, _view = _bento(n_blocks, nlog)
        assert fs.geo.nlog == want
        assert fs.journal.capacity == want - 1


def _hash_binding(dev, **kw):
    """Services hashing with the chip's blockhash (its host reference),
    whose hash of an all-zero block is 0, as a zeroed header entry's."""
    return KernelServices(dev, checksum=blockhash_np, **kw)


def _full_width_sweep(monkeypatch, payload, *, tear_headers=False):
    """One large write that spans at least three ~494-block commits of a
    511-block log, then an fsync, crashed at the sampled points (the
    dying write lands half a block). With ``tear_headers`` the points
    include each commit's header write. Recovery replays a whole commit
    or nothing, and the file reads back as the prefix some completed
    sub-op wrote."""
    sizes, replayed, header_points = [], [], []
    write_commit, recover = Journal._write_commit, Journal.recover

    def counted_commit(self):
        sizes.append(len(self._pending))
        # the log's blocks land one write each, then the header
        header_points.append(self.ks._dev._writes_seen + len(self._pending))
        write_commit(self)

    def counted_recover(self):
        replayed.append(recover(self))
        return replayed[-1]

    monkeypatch.setattr(Journal, "_write_commit", counted_commit)
    monkeypatch.setattr(Journal, "recover", counted_recover)
    sim = CrashSim(lambda: Xv6FileSystem(Xv6Options(
        group_commit=True, batched_install=True)), n_blocks=8192, nlog=511)

    def setup(ctx):
        ctx.view.create("/big")

    def workload(ctx):
        header_points.clear()
        ctx.dev.fail_torn_bytes = L.BSIZE // 2
        ctx.view.write_file("/big", payload, create=False)
        ctx.view.fsync("/big")

    total = sim.measure(workload, setup=setup)
    torn_headers = set(header_points) if tear_headers else set()
    full = {s for s in sizes if s >= L.LOG_MAX_ENTRIES - MAXOP_BLOCKS}
    assert sum(s in full for s in sizes) >= 3, sizes
    commits = set(sizes)

    def invariant(rec):
        assert replayed[-1] in commits | {0}, replayed[-1]
        assert L.SuperBlock.unpack(rec.dev.read_block(0)).magic == L.FSMAGIC
        if rec.crash_point in torn_headers:  # a half-written commit record
            hdr = rec.dev.read_block(rec.fs.geo.logstart)
            assert struct.unpack_from("<I", hdr)[0] == L.LOG_MAGIC
            assert hdr[L.BSIZE // 2:] == bytes(L.BSIZE // 2)
            assert replayed[-1] == 0
        got = rec.view.read_file("/big")
        assert got == payload[:len(got)], "recovered bytes are not a prefix"
        sub_op = (MAXOP_BLOCKS - rec.fs._chain_write_overhead) * L.BSIZE
        assert len(got) == len(payload) or len(got) % sub_op == 0, len(got)
        if not rec.crashed:
            assert got == payload
        rec.view.statfs()

    points = sorted(set(quick_points(total)) | torn_headers)
    assert sim.sweep(workload, invariant, setup=setup, points=points) >= 12
    assert set(replayed) & full, "no sampled point replayed a full commit"


def test_full_width_commits_survive_every_sampled_crash(monkeypatch):
    _full_width_sweep(monkeypatch, _payload(1600))


def test_full_width_commits_of_zero_blocks_survive_torn_headers(monkeypatch):
    """The same sweep over zero-filled blocks hashed by blockhash, each
    commit's header write torn too: a torn header's zeroed entries would
    verify against zero blocks, so only the record's own crc32 keeps
    recovery from replaying them."""
    monkeypatch.setattr(crashsim, "kernel_binding", _hash_binding)
    _full_width_sweep(monkeypatch, bytes(1600 * L.BSIZE), tear_headers=True)


@pytest.mark.parametrize("magic", [L.LOG_MAGIC, L.LOG_MAGIC_V1],
                         ids=["crc32", "v1"])
def test_torn_header_of_full_width_commit_is_not_replayed(magic):
    """A 510-entry commit of zero-filled blocks whose header write tears at
    half a block: the rest keeps the cleared header's zeros, so entries
    255.. read (home 0, checksum 0), which verifies against a zero block
    under blockhash. Recovery replays nothing, and above all no zeros over
    the superblock: the record's crc32 refuses it, and a record of the
    format before the crc32 is refused by its homes."""
    assert blockhash_np(bytes(L.BSIZE)) == 0
    dev = MemBlockDevice(8192)
    ks = _hash_binding(dev)
    mkfs(ks, nlog=L.NLOG_MAX)
    sb = dev.read_block(0)
    geo = L.SuperBlock.unpack(sb)
    j = Journal(ks, ks.superblock(), geo)
    for home in range(geo.datastart, geo.datastart + j.capacity):
        j.log_write(home, bytes(L.BSIZE))
    dev._writes_seen = 0
    dev.fail_after_writes = j.capacity  # the log lands, its record tears
    dev.fail_torn_bytes = L.BSIZE // 2
    with pytest.raises(BlockDeviceError):
        j.commit()
    dev.fail_after_writes = -1
    hdr = dev.read_block(geo.logstart)
    assert struct.unpack_from("<II", hdr) == (L.LOG_MAGIC, j.capacity)
    assert hdr[L.BSIZE // 2:] == bytes(L.BSIZE // 2)
    if magic == L.LOG_MAGIC_V1:
        dev.write_block(geo.logstart, struct.pack("<I", magic) + hdr[4:])
    ks2 = _hash_binding(dev)
    assert Journal(ks2, ks2.superblock(), geo).recover() == 0
    assert dev.read_block(0) == sb
    mf = remount(dev)
    assert mf.mount.module.journal.capacity == L.LOG_MAX_ENTRIES
    mf.view.write_file("/f", b"after")
    assert mf.view.read_file("/f") == b"after"


def test_commit_record_with_a_changed_home_is_not_replayed():
    """A record whose entries all verify, but one of whose homes changed
    on the device, is refused by its crc32: replaying it would write a
    block over another's home."""
    dev = MemBlockDevice(8192)
    ks = kernel_binding(dev)
    mkfs(ks, nlog=L.NLOG_MAX)
    geo = L.SuperBlock.unpack(dev.read_block(0))
    j = Journal(ks, ks.superblock(), geo)
    for i in range(4):
        j.log_write(geo.datastart + 2 * i, bytes([i + 1]) * L.BSIZE)
    dev._writes_seen = 0
    dev.fail_after_writes = 5  # the log and its record land
    with pytest.raises(BlockDeviceError):
        j.commit()
    dev.fail_after_writes = -1
    hdr = bytearray(dev.read_block(geo.logstart))
    off = struct.calcsize(L.LOG_HEAD_FMT)
    (home,) = struct.unpack_from("<I", hdr, off)
    struct.pack_into("<I", hdr, off, home + 1)  # another data block
    dev.write_block(geo.logstart, bytes(hdr))
    ks2 = kernel_binding(dev)
    assert Journal(ks2, ks2.superblock(), geo).recover() == 0
    assert dev.read_block(home + 1) == bytes(L.BSIZE)


@pytest.mark.parametrize("torn", [None, 0, L.LOG_MAX_ENTRIES - 1])
def test_torn_block_in_full_width_commit_is_detected(torn):
    """A 510-entry commit record whose log landed but whose install did
    not: recovery installs all 510 blocks, or none where one log block is
    torn (first or last entry of the record)."""
    dev = MemBlockDevice(8192)
    ks = kernel_binding(dev)
    mkfs(ks, nlog=L.NLOG_MAX)
    geo = L.SuperBlock.unpack(dev.read_block(0))
    j = Journal(ks, ks.superblock(), geo)
    assert j.capacity == L.LOG_MAX_ENTRIES
    homes = range(geo.datastart, geo.datastart + j.capacity)
    blocks = [_payload(1)[:L.BSIZE - 4] + i.to_bytes(4, "little")
              for i in range(j.capacity)]
    for home, data in zip(homes, blocks):
        j.log_write(home, data)
    dev._writes_seen = 0
    dev.fail_after_writes = j.capacity + 1  # the log and its record land
    with pytest.raises(BlockDeviceError):
        j.commit()
    dev.fail_after_writes = -1
    if torn is not None:
        dev.write_block(geo.logstart + 1 + torn, b"TORN" * (L.BSIZE // 4))
    ks2 = kernel_binding(dev)
    got = Journal(ks2, ks2.superblock(), geo).recover()
    installed = [dev.read_block(h) for h in homes]
    if torn is None:
        assert got == L.LOG_MAX_ENTRIES and installed == blocks
    else:
        assert got == 0
        assert installed == [bytes(L.BSIZE)] * len(blocks)


@pytest.mark.parametrize("n_blocks, nlog, capacity", [
    (65536, None, 255),   # the log the rule gives the device
    (8192, 511, 510),     # the widest log
])
def test_large_write_commits_once_per_log_width(profile, n_blocks, nlog,
                                                capacity):
    """A write of N blocks commits at most ceil(N / (capacity - 16)) + 2
    times: the sub-op reservation, not a 64-block log, sets the count."""
    fs, view = _bento(n_blocks, nlog)
    assert fs.journal.capacity == capacity
    n = 1500
    view.create("/big")
    view.fsync("/big")
    with profile():
        c0 = fs.journal.commits
        view.write_file("/big", _payload(n), create=False)
        view.fsync("/big")
        commits = fs.journal.commits - c0
    assert 0 < commits <= -(-n // (capacity - MAXOP_BLOCKS)) + 2, commits
    t = spans.snapshot()
    assert t["spans"]["journal.commit"]["count"] == commits
    assert n <= t["counters"]["journal.commit_blocks"] <= commits * capacity
    assert view.read_file("/big") == _payload(n)


def test_device_with_a_64_block_log_remounts_and_recovers():
    """A device formatted with the fixed 64-block log of old keeps it,
    though the rule would now give it 128: a commit whose record landed
    but whose install did not is replayed by a cold remount."""
    dev = MemBlockDevice(32768)
    assert L.log_blocks(dev.n_blocks) == 128
    ks = kernel_binding(dev)
    mkfs(ks, nlog=64)
    fs = Xv6FileSystem(Xv6Options())
    fs.init(ks.superblock(), ks)
    view = PosixView(DirectMount(fs))
    data = _payload(20)
    view.write_file("/f", data)
    staged = len(fs.journal._pending)
    assert 20 < staged < fs.journal.capacity
    dev._writes_seen = 0
    dev.fail_after_writes = staged + 1  # the log and its record land
    with pytest.raises(BlockDeviceError):
        fs.journal.commit()
    dev.fail_after_writes = -1
    mf = remount(dev)
    assert mf.mount.module.geo.nlog == 64
    assert mf.mount.module.journal.capacity == 63
    assert mf.view.read_file("/f") == data


@pytest.mark.parametrize("n_blocks, nlog, capacity", [
    (16384, None, 63),
    (8192, 511, 510),
])
def test_binding_the_journal_warms_its_commit_buckets(n_blocks, nlog,
                                                      capacity):
    """The journal asks its services to compile the hash of every commit
    size its log allows, once, when it is bound."""
    warmed = []
    dev = MemBlockDevice(n_blocks)
    ks = KernelServices(dev, checksum=blockhash_np,
                        warm_checksum_batch=warmed.append)
    mkfs(ks, nlog=nlog)
    fs = Xv6FileSystem(Xv6Options())
    fs.init(ks.superblock(), ks)
    assert warmed == [capacity] == [fs.journal.capacity]


def test_warm_batch_compiles_every_bucket_a_commit_launches(monkeypatch):
    """Warming up to 510 blocks compiles the seven buckets 1..510 blocks
    pad to, and after a warm-up no batch of that range compiles."""
    shapes = []
    monkeypatch.setattr(bh_ops, "_warm_bucket",
                        lambda rows, block_rows, interpret:
                        shapes.append((rows, block_rows)))
    bh_ops.warm_batch(L.LOG_MAX_ENTRIES)
    assert shapes == [(8, 8), (16, 16), (32, 32), (64, 64), (128, 128),
                      (256, 256), (512, 256)]
    monkeypatch.undo()
    bh_ops.warm_batch(20, interpret=True)
    compiled = bh_ops.hash_rows._cache_size()
    for n in (1, 8, 9, 16, 17, 20):
        blocks = [bytes([n]) * L.BSIZE] * n
        assert bh_ops.checksum_batch(blocks, interpret=True) == \
            [blockhash_np(blocks[0])] * n
    assert bh_ops.hash_rows._cache_size() == compiled
