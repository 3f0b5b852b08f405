"""Percent of the blocks the buffer cache served in the window that a
bulk read gathered, with no buffer head per block (``cache.bulk_blocks``
over it plus ``cache.bread_many_blocks``). The restore's reads past the
cache's capacity take the bulk path; indirect blocks and small reads,
the saves' among them, go through heads."""

from benchkit.program import counter, per


def read(record):
    bulk = counter("cache.bulk_blocks")
    heads = counter("cache.bread_many_blocks")
    return per(bulk, (bulk or 0) + (heads or 0), 100.0)
