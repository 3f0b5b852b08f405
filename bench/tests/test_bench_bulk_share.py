"""``cache.bulk_share.resume``: the share of the blocks the buffer cache
served that a bulk read gathered. A read past a small cache's capacity,
taken under a profile, reads as nearly all bulk; a program without the
counters, or without spans at all, reads nothing and does not raise."""

import sys

import jax
import numpy as np
import pytest

import benchtiny  # noqa: F401 — puts bench/ and src/ on the path
from benchkit import program, spec

NAME = "cache.bulk_share.resume"


def test_declared_for_the_ckpt_cell_only():
    m = next(m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == NAME)
    assert (m["unit"], m["moves"], m["workloads"]) == (
        "%", "resume_s", ["ckpt-smollm135m.cycle"])


def test_a_read_past_the_cache_reads_nearly_all_bulk(tmp_path):
    from repro.core.registry import mount as bento_mount
    from repro.core.services import kernel_binding
    from repro.fs.blockdev import MemBlockDevice
    from repro.fs.posix import PosixView
    from repro.fs.xv6 import Xv6FileSystem, Xv6Options, mkfs

    ks = kernel_binding(MemBlockDevice(4096), cache_capacity=64)
    mkfs(ks)
    m = bento_mount("xv6", ks, module=Xv6FileSystem(
        Xv6Options(group_commit=True, batched_install=True)))
    v = PosixView(m)
    data = np.random.default_rng(0).integers(
        0, 256, 1200 * 4096, np.uint8).tobytes()
    v.write_file("/f", data)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            assert v.read_many(["/f"]) == [data]
        share = spec.metric_reader(NAME)({})
    finally:
        m.unmount()
    # 1,200 blocks gathered in bulk; only the small reads, if any, took
    # heads
    assert program.counter("cache.bulk_blocks") == 1200
    assert 99.0 <= share <= 100.0


@pytest.mark.parametrize("counters,want", [
    ({}, None),
    ({"cache.bread_many_blocks": 10}, 0.0),
    ({"cache.bulk_blocks": 30, "cache.bread_many_blocks": 10}, 75.0),
    ({"cache.bulk_blocks": 30}, 100.0),
])
def test_share_of_the_counters(counters, want, monkeypatch):
    monkeypatch.setattr(program, "_table",
                        lambda: {"spans": {}, "counters": counters})
    assert spec.metric_reader(NAME)({}) == want


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert spec.metric_reader(NAME)({}) is None
