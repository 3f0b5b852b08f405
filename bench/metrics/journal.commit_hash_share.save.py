"""Percent of the journal's commit time spent hashing the commit's blocks
and writing the commit record (``journal.commit.hash`` over
``journal.commit``)."""

from benchkit.program import per, span_total


def read(record):
    return per(span_total("journal.commit.hash"),
               span_total("journal.commit"), 100.0)
