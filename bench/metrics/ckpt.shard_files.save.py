"""Shard files a save wrote (``ckpt.save.shard_files`` over the count of
``ckpt.save``)."""

from benchkit.program import counter, per, span_count


def read(record):
    files = counter("ckpt.save.shard_files")
    return None if files is None else per(files, span_count("ckpt.save"))
