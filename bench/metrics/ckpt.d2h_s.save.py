"""Seconds per save that the checkpoint store spends copying the state
from the device to the host (``ckpt.save.d2h`` spans over ``ckpt.save``
spans)."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("ckpt.save.d2h"), span_count("ckpt.save"))
