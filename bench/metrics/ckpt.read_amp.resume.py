"""Bytes the file system returned to the window's restores, verification
reads included, per byte those restores put on devices
(``ckpt.restore.bytes_read`` over ``ckpt.restore.bytes_placed``)."""

from benchkit.program import counter, per


def read(record):
    return per(counter("ckpt.restore.bytes_read"),
               counter("ckpt.restore.bytes_placed"))
