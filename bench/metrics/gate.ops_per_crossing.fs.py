"""fs operations per crossing of the mount's op gate
(``OpGate.crossings``): how many submissions one drain carries."""

from benchkit.readers import ratio


def read(record):
    return ratio(record, "fs", "ops", "gate_crossings")
