"""Seconds per save spent in journal commits (``journal.commit`` spans
over ``ckpt.save`` spans): the saves' own commits and those of the
retention that follows each save."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("journal.commit"), span_count("ckpt.save"))
