"""Seconds of one cold remount (``mount.remount``): the kernel binding
and its blockhash probe, and the module's init with journal recovery."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("mount.remount"), span_count("mount.remount"))
