"""Blocks the device took during the saves (``MemBlockDevice.writes``)
per block of state saved: the journal's log and home writes and the
metadata, over the payload."""

from benchkit.readers import ratio


def read(record):
    return ratio(record, "save", "dev_writes", "data_blocks")
