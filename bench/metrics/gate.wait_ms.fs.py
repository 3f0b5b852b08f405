"""Mean milliseconds a submission waits in ``Mount.submit`` until a
drain, its own or another thread's, takes it (``gate.wait``)."""

from benchkit.program import per, span_count, span_total


def read(record):
    return per(span_total("gate.wait"), span_count("gate.wait"), 1e3)
