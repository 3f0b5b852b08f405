"""Directory entries an xv6 lookup reads, on average: the slots from the
start of the directory to the name, or to the end on a miss
(``dir.entries_scanned`` over ``dir.lookups``)."""

from benchkit.program import counter, per


def read(record):
    return per(counter("dir.entries_scanned"), counter("dir.lookups"))
