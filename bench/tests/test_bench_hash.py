"""The hash share counts the same work whatever implements the hash: the
bytes the file system asks to have hashed and the time until the answer
is back, read at the kernel-services boundary."""

import pytest

import benchtiny  # noqa: F401 — puts bench/ and src/ on the path
from benchkit.meter import HashMeter
from benchkit.readers import hash_roofline


def _workload(monkeypatch, force_pallas: bool):
    from repro.fs.mounts import make_mount

    if force_pallas:
        monkeypatch.setenv("REPRO_FORCE_PALLAS_CHECKSUM", "1")
    else:
        monkeypatch.delenv("REPRO_FORCE_PALLAS_CHECKSUM", raising=False)
    mf = make_mount("bento")
    meter = HashMeter()
    meter.attach(mf.services)
    meter.phase = "fs"
    view = mf.view
    for i in range(3):
        view.write_many([(f"/f{i}", bytes([i]) * (4096 * (i + 1) + 7))])
        view.fsync(f"/f{i}")
    view.read_many(["/f0", "/f1", "/f2"])
    meter.phase = None
    return mf.services.checksum_impl, meter.totals["fs"]


def test_equal_bytes_under_host_crc_and_blockhash(monkeypatch):
    crc_impl, crc = _workload(monkeypatch, force_pallas=False)
    bh_impl, bh = _workload(monkeypatch, force_pallas=True)
    assert crc_impl == "crc32" and bh_impl == "blockhash-interpret"
    assert crc["bytes"] == bh["bytes"] > 0
    assert crc["calls"] == bh["calls"] > 0
    for t in (crc, bh):
        share = hash_roofline({"hash": {"fs": t},
                               "peaks": benchtiny.CPU_PEAKS}, "fs")
        assert 0 < share < 100


def test_no_hash_calls_reads_nothing():
    assert hash_roofline({"hash": {}, "peaks": benchtiny.CPU_PEAKS},
                         "save") is None


@pytest.mark.parametrize("phase", [None, "save"])
def test_calls_outside_a_phase_are_not_counted(phase):
    from repro.core.services import KernelServices
    from repro.fs.blockdev import MemBlockDevice

    ks = KernelServices(MemBlockDevice(64), checksum=len)
    meter = HashMeter()
    meter.attach(ks)
    meter.phase = phase
    assert ks.checksum_batch([b"ab", b"cde"]) == [2, 3]
    assert ks.checksum(b"xyz") == 3
    want = {} if phase is None else {"save": {"bytes": 8, "calls": 2}}
    assert {k: {"bytes": v["bytes"], "calls": v["calls"]}
            for k, v in meter.totals.items()} == want


@pytest.mark.parametrize("nbytes", [0, 3, 4096, 4096 * 63 + 5,
                                    (4 << 20) + 4096 + 2])
def test_reference_hash_agrees_with_the_programs(nbytes):
    """The comparison's own ``blockhash`` (chunked past 4 MiB) gives what
    the program's host definition and its kernel give."""
    import numpy as np
    from benchkit import refs
    from repro.kernels.blockhash import ops
    from repro.kernels.blockhash.ref import blockhash_np

    data = np.random.default_rng(nbytes).bytes(nbytes)
    assert refs.blockhash(data) == blockhash_np(data)
    if nbytes <= 4096 * 64:
        assert refs.blockhash(data) == ops.checksum(data, interpret=True)
