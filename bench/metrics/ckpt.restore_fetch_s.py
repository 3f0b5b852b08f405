"""Seconds the checkpoint store's restore spent fetching shard bytes
through the file system (``load(stats=)``'s ``pipeline.fetch_s``), the mean
over the window's resumes."""


def read(record):
    xs = record["samples"].get("restore_fetch_s")
    return sum(xs) / len(xs) if xs else None
