"""Seconds per resume spent placing restored shards onto the devices
(``ckpt.restore.put`` over the count of ``ckpt.restore``)."""

from benchkit.program import per, span_count, span_total


def read(record):
    put = span_total("ckpt.restore.put")
    return None if put is None else per(put, span_count("ckpt.restore"))
