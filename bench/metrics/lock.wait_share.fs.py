"""Percent of the time fs operations spent inside the mount's op gate
(``gate.drain`` and scalar ``gate.call`` spans) that threads spent
parked on a contended fs lock domain (``lock.wait``)."""

from benchkit.program import per, span_total


def read(record):
    drains, calls = span_total("gate.drain"), span_total("gate.call")
    inside = (drains or 0.0) + (calls or 0.0)
    return per(span_total("lock.wait"), inside, 100.0)
