"""Seconds per save spent hashing whole shard files for the manifest
(``ckpt.save.shard_hash`` spans over ``ckpt.save`` spans)."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("ckpt.save.shard_hash"), span_count("ckpt.save"))
