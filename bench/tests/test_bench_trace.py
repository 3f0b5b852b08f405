"""The reduction from a profiler trace to busy and idle time, the top
device operations and the idle time by harness span, on planes laid out
as ``jax.profiler.ProfileData`` gives them: ``/host:`` planes whose lines
carry the ``bench.`` annotations, ``/device:`` planes with an ``XLA Ops``
line."""

from types import SimpleNamespace

import pytest

import benchtiny  # noqa: F401 — puts bench/ and src/ on the path
from benchkit import trace

def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_gap_goes_to_the_innermost_span():
    spans = sorted([(0, 100, "bench.outer"), (10, 20, "bench.inner"),
                    (50, 60, "bench.other")])
    gaps = [(15, 4), (30, 2), (55, 1), (200, 8)]
    assert dict(trace._charge(gaps, spans)) == {
        "bench.inner": 4, "bench.outer": 2, "bench.other": 1,
        "(no span)": 8}


def _plane(name, lines):
    ev = SimpleNamespace
    return ev(name=name, lines=[
        ev(name=ln, events=[ev(name=n, start_ns=lo, end_ns=hi)
                            for n, lo, hi in events])
        for ln, events in lines.items()])


def _two_chips():
    host = _plane("/host:CPU", {"main": [
        ("bench.window", 100, 200), ("bench.save", 100, 150),
        ("jit_step", 100, 200)]})
    return [host] + [_plane(f"/device:TPU:{i}", {"XLA Ops": ops, "Steps": [
        ("step", 0, 300)]}) for i, ops in enumerate([
            [("hash", 90, 120), ("hash", 110, 130)],  # 30 ns in the window
            [("copy", 160, 170), ("copy", 190, 260)]])]  # 20 ns


def test_two_chips_are_averaged_and_clipped_to_the_window():
    out = trace.reduce_planes(_two_chips())
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(25e-9)
    # op time sums each op's own clipped length, overlaps and all
    assert dict(out["device_ops"]) == pytest.approx(
        {"hash": 20e-9, "copy": 10e-9})
    # a gap goes whole to the span over its midpoint: chip 0's one gap
    # (130-200) to none, chip 1's 100-160 to the save and 170-190 to none
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"bench.save": 30e-9, "(no span)": 45e-9})


def test_trace_without_the_window_or_a_device_reads_nothing():
    planes = _two_chips()
    assert trace.reduce_planes(planes, window="bench.no_such_span") is None
    assert trace.reduce_planes(planes[:1]) is None
