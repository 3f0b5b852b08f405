"""Percent of buffer-cache lookups in the window that hit
(``BufferCache.hits`` over hits and misses)."""


def read(record):
    c = record["counters"].get("fs")
    if not c or not c["cache_hits"] + c["cache_misses"]:
        return None
    return 100.0 * c["cache_hits"] / (c["cache_hits"] + c["cache_misses"])
