"""Percent of the traced window in which the device ran no operation,
from the profiler's trace, averaged over the chips used."""

from benchkit.readers import idle_share


def read(record):
    return idle_share(record)
