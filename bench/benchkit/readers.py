"""Arithmetic the per-layer metric readers share. A reader that finds
nothing to read returns None, never 0."""

from __future__ import annotations

from typing import Dict, Optional


def hash_roofline(record: Dict, phase: str) -> Optional[float]:
    """Percent of the HBM roofline in the services' hash calls of one
    phase: the least time the chip needs to stream the bytes the file
    system asked to have hashed, over the wall time until the answers
    were back."""
    t = record["hash"].get(phase)
    if not t or t["seconds"] <= 0 or t["bytes"] <= 0:
        return None
    return 100.0 * t["bytes"] / record["peaks"]["hbm_bytes_per_s"] \
        / t["seconds"]


def idle_share(record: Dict) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips used."""
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def ratio(record: Dict, group: str, num: str, den: str,
          scale: float = 1.0) -> Optional[float]:
    c = record["counters"].get(group)
    if not c or not c.get(den):
        return None
    return scale * c[num] / c[den]
