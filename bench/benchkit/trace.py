"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle
time over the harness's window span, the device operations that took
most time, and the idle time charged to the harness span the host was
in when the device sat idle.

Busy time is the union of the intervals of the operations on each
device's ``XLA Ops`` line, clipped to the window and averaged over the
devices that ran any. An idle gap is charged to the innermost harness
span (a ``bench.`` annotation on any host thread) that covers its
midpoint, or to ``(no span)``.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _charge(gaps, spans) -> Dict[str, float]:
    """Sum each gap's length under the innermost span covering its
    midpoint; ``gaps`` sorted by midpoint, ``spans`` by start."""
    out: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, float, str]] = []
    j = 0
    for mid, length in gaps:
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > mid]
        owner = (min(active, key=lambda s: s[1] - s[0])[2] if active
                 else "(no span)")
        out[owner] += length
    return out


def reduce(path: str, window: str = WINDOW) -> Optional[Dict]:
    """``None`` where the trace holds no window span or no device op."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window)


def reduce_planes(planes, window: str = WINDOW) -> Optional[Dict]:
    """``reduce`` over planes with ``name`` and ``lines``, each line with
    ``name`` and ``events`` that have ``name``, ``start_ns``, ``end_ns``."""
    spans: List[Tuple[float, float, str]] = []
    devices: List[List[Tuple[float, float, str]]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
        elif plane.name.startswith("/device:"):
            ops = [(e.start_ns, e.end_ns, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
    wins = [(lo, hi) for lo, hi, name in spans if name == window]
    if not wins or not devices:
        return None
    w_lo, w_hi = wins[0]
    op_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []  # (midpoint, length)
    busy_ns = 0.0
    for ops in devices:
        clipped = [(max(lo, w_lo), min(hi, w_hi), name)
                   for lo, hi, name in ops if hi > w_lo and lo < w_hi]
        for lo, hi, name in clipped:
            op_ns[name] += hi - lo
        busy = _union([(lo, hi) for lo, hi, _ in clipped])
        busy_ns += sum(hi - lo for lo, hi in busy)
        edges = [w_lo] + [x for iv in busy for x in iv] + [w_hi]
        gaps += [((lo + hi) / 2, hi - lo)
                 for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo]
    idle_ns = _charge(sorted(gaps), sorted(
        (lo, hi, name) for lo, hi, name in spans if name != window))
    n = len(devices)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (w_hi - w_lo) / 1e9,
            "devices": n, "device_ops": top(op_ns), "idle_gaps": top(idle_ns)}
