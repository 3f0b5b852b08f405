"""Program spans and counters (``repro.core.spans``): they record only
while a profile is taken, self time excludes child spans, each profile
starts a fresh table, concurrent threads lose no count, and the spans
land on the profile's host plane. Also what the instrumented layers
count: directory entries per lookup, blocks per journal commit, and one
``gate.wait`` per submission."""

import sys
import threading
import time

import jax
import pytest

from repro.core import spans
from repro.core.interface import FsError, ROOT_INO, SubmissionEntry


@pytest.fixture
def profile(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return lambda: jax.profiler.trace(str(tmp_path),
                                      profiler_options=opts)


def test_no_profile_records_nothing():
    before = spans.snapshot()
    s = spans.span("test.off")
    with s:
        assert s._ann is None  # no annotation is built
        spans.count("test.off_counter", 5)
    assert spans.clock() is None
    assert spans.snapshot() == before


def test_nested_spans_count_total_and_self(profile):
    with profile():
        for _ in range(2):
            with spans.span("test.outer"):
                time.sleep(0.01)
                with spans.span("test.inner"):
                    time.sleep(0.02)
        spans.count("test.counter")
        spans.count("test.counter", 4)
    t = spans.snapshot()
    outer, inner = t["spans"]["test.outer"], t["spans"]["test.inner"]
    assert outer["count"] == inner["count"] == 2
    assert inner["total_s"] >= 0.04 and inner["self_s"] == inner["total_s"]
    assert outer["total_s"] >= 0.06
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert outer["self_s"] >= 0.02
    assert t["counters"] == {"test.counter": 5}


def test_interval_is_a_child_of_the_open_span(profile):
    with profile():
        with spans.span("test.parent"):
            t0 = spans.clock()
            time.sleep(0.01)
            spans.interval("test.waited", time.perf_counter() - t0)
    t = spans.snapshot()["spans"]
    waited, parent = t["test.waited"], t["test.parent"]
    assert waited["count"] == 1 and waited["self_s"] >= 0.01
    assert parent["self_s"] == pytest.approx(
        parent["total_s"] - waited["total_s"], abs=1e-9)


def test_a_second_profile_starts_a_fresh_table(profile):
    with profile():
        with spans.span("test.first"):
            pass
        spans.count("test.first_counter")
    assert "test.first" in spans.snapshot()["spans"]
    with profile():
        with spans.span("test.second"):
            pass
    t = spans.snapshot()
    assert set(t["spans"]) == {"test.second"} and t["counters"] == {}


def test_concurrent_threads_lose_no_count(profile):
    n_threads, n = 8, 2000
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(n):
            with spans.span("test.threads"):
                spans.count("test.thread_counter")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with profile():
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    t = spans.snapshot()
    assert t["spans"]["test.threads"]["count"] == n_threads * n
    assert t["counters"]["test.thread_counter"] == n_threads * n


def test_spans_land_on_the_profiles_host_plane(tmp_path, profile):
    from jax.profiler import ProfileData

    with profile():
        with spans.span("test.on_the_trace"):
            time.sleep(0.001)
    paths = list(tmp_path.rglob("*.xplane.pb"))
    assert paths
    names = {e.name for p in ProfileData.from_file(str(paths[0])).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert "test.on_the_trace" in names


def test_directory_lookups_count_the_entries_read(profile):
    from repro.fs.mounts import make_mount

    mf = make_mount("bento")
    for i in range(10):
        mf.view.write_file(f"/f{i}", b"x")
    with profile():
        mf.mount.call("lookup", ROOT_INO, "f4")  # slots f0..f4
        with pytest.raises(FsError):
            mf.mount.call("lookup", ROOT_INO, "nope")  # all 10 slots
    c = spans.snapshot()["counters"]
    assert c["dir.lookups"] == 2
    assert c["dir.entries_scanned"] == 5 + 10


def test_journal_commits_count_their_blocks_and_submissions_wait(profile):
    from repro.fs.mounts import make_mount

    mf = make_mount("bento")
    journal = mf.mount.module.journal
    with profile():
        n0 = journal.commits
        comps = mf.mount.submit([SubmissionEntry("create", (ROOT_INO, "a")),
                                 SubmissionEntry("create", (ROOT_INO, "b"))])
        assert all(c.ok for c in comps)
        staged = len(journal._pending)
        mf.view.fsync("/a")
        assert journal.commits == n0 + 1
    t = spans.snapshot()
    assert t["spans"]["gate.wait"]["count"] == 1
    assert t["spans"]["journal.commit"]["count"] == 1
    assert t["counters"]["journal.commit_blocks"] == staged > 0
    commit = t["spans"]["journal.commit"]
    steps = sum(t["spans"][f"journal.commit.{s}"]["total_s"]
                for s in ("log_write", "hash", "install"))
    assert commit["self_s"] == pytest.approx(commit["total_s"] - steps,
                                             abs=1e-9)


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_checkpoint_counters_on_a_resharding_restore(profile, depth):
    """A save cut 4 ways and a restore onto a 2 x 2 grid of another cut:
    the save counts the manifest's shard files, the restore the bytes it
    placed (the target cells') and read (at least those)."""
    import numpy as np

    from repro import checkpoint as ckpt
    from repro.distributed.resharding import ShardGrid, index_volume
    from repro.fs.mounts import make_mount

    mf = make_mount("bento")
    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(8, 16)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32)}
    src = {"w": ShardGrid.from_spec((8, 16), (None, "data"), {"data": 4}),
           "b": ShardGrid.from_spec((16,), ("data",), {"data": 4})}
    dst = {"w": ShardGrid.from_spec((8, 16), ("model", "data"),
                                    {"data": 2, "model": 2}),
           "b": ShardGrid.from_spec((16,), ("data",), {"data": 2})}
    cks = mf.services.checksum
    with profile():
        manifest = ckpt.save(mf.view, "/ck", tree, step=1, checksum=cks,
                             shardings=src)
    assert spans.snapshot()["counters"]["ckpt.save.shard_files"] == sum(
        len(r["shards"]) for r in manifest["leaves"]) == 8
    with profile():
        back, _ = ckpt.load(mf.view, "/ck", tree, checksum=cks,
                            sharding_tree=dst, pipeline_depth=depth)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]), tree[k])
    c = spans.snapshot()
    target = sum(index_volume(cell) * 4 for g in dst.values()
                 for cell in g.indices())
    assert c["counters"]["ckpt.restore.bytes_placed"] == target
    assert c["counters"]["ckpt.restore.bytes_read"] >= target
    assert c["counters"]["ckpt.restore.runs"] > 0
    assert c["spans"]["ckpt.restore.put"]["count"] == 2
