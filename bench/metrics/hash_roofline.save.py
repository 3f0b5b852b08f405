"""Percent of the HBM roofline in the kernel services' hash calls during
the save phase: bytes the fs asked to have hashed over the chip's HBM
bandwidth, over the seconds until the answers were back. Read at the
``KernelServices.checksum``/``checksum_batch`` boundary, so it counts the
same work whatever implements the hash."""

from benchkit.readers import hash_roofline


def read(record):
    return hash_roofline(record, "save")
