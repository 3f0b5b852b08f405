"""KiB per offset-read run the window's restores issued
(``ckpt.restore.run_bytes`` over ``ckpt.restore.runs``): how far a
reshard's slices cut the reads below whole shard files."""

from benchkit.program import counter, per


def read(record):
    return per(counter("ckpt.restore.run_bytes"),
               counter("ckpt.restore.runs"), 1 / 1024)
