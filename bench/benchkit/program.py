"""The program's own spans and counters (``repro.core.spans``), as the
per-layer metric readers take them: the table of the newest profile,
which the traced run takes over the window alone. Each function returns
None where the program has no such span or counter, or no module that
records them, never 0."""

from __future__ import annotations

from typing import Optional


def _table() -> Optional[dict]:
    try:
        from repro.core import spans
    except ImportError:  # a program without spans
        return None
    return spans.snapshot()


def span_total(name: str) -> Optional[float]:
    """Seconds summed over every ``name`` span."""
    t = _table()
    s = t and t["spans"].get(name)
    return s["total_s"] if s else None


def span_count(name: str) -> Optional[int]:
    t = _table()
    s = t and t["spans"].get(name)
    return s["count"] if s else None


def counter(name: str) -> Optional[int]:
    t = _table()
    return t["counters"].get(name) if t else None


def per(num: Optional[float], den: Optional[float],
        scale: float = 1.0) -> Optional[float]:
    """``scale * num / den``; None where the denominator is missing or 0.
    A missing numerator beside a denominator reads 0: the program
    records spans there and this one never happened."""
    if not den:
        return None
    return scale * (num or 0.0) / den
