"""Mean microseconds of one batched blockhash call (``blockhash.batch``):
packing the blocks, the transfer, the launch and the wait for the
answer. In this cell they are mostly the saves' 63-block journal
commits."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("blockhash.batch"), span_count("blockhash.batch"),
               1e6)
