"""Percent of the restore's fetch time spent reading missed blocks from
the device into the buffer cache (``cache.fill`` over
``ckpt.restore.fetch``). The fills of the saves and remounts are in
the numerator too."""

from benchkit.program import per, span_total


def read(record):
    return per(span_total("cache.fill"), span_total("ckpt.restore.fetch"),
               100.0)
