"""Program spans and counters, recorded only while a profile is taken.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: it lands on the
host plane of the same trace as the device's ops, on the same clock.
While a profile is being taken (``jax.profiler.trace``,
``start_trace``/``stop_trace``, or a client of ``start_server``) it also
adds to a process-wide table: a count, total seconds, and self seconds
(total minus the child spans on the same thread). ``count(name, n)``
adds to a counter, and ``snapshot()`` returns the table of the newest
profile. With no profile a span costs one ``is_enabled()`` call and
builds no annotation, so the spans stay in the code for good.

Each thread accumulates into a table of its own, so recording takes no
shared lock; ``snapshot()`` merges them. A call made with no profile
marks the table stale, and the first recording call of the next profile
starts a fresh one.

``clock()`` and ``interval(name, seconds)`` record a span whose end
another thread observes (a submission's wait for a drain): it goes into
the table as a child of this thread's open span, and is not an
annotation on the trace.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional

# jax.profiler.TraceAnnotation's base class: its static is_enabled() is
# true while a profile is taken. Importing it does not import jax.
from jaxlib._profiler import TraceMe

_on = TraceMe.is_enabled
_clock = time.perf_counter
_local = threading.local()
_lock = threading.Lock()
_gen = 0          # the profile the current tables belong to
_stale = True     # a call saw no profile: the next recording starts anew
_tables: List["_Table"] = []
_annotation = None  # jax.profiler.TraceAnnotation, once a profile needs it


class _Table:
    __slots__ = ("gen", "spans", "counters")

    def __init__(self, gen: int):
        self.gen = gen
        self.spans: Dict[str, List[float]] = {}  # name -> [n, total, self]
        self.counters: Dict[str, int] = {}


def _table() -> _Table:
    """This thread's table of the current profile."""
    global _gen, _stale
    if _stale:
        with _lock:
            if _stale:
                _stale = False
                _gen += 1
                _tables.clear()
    t = getattr(_local, "table", None)
    if t is None or t.gen != _gen:
        t = _local.table = _Table(_gen)
        with _lock:
            _tables.append(t)
    return t


def _stack() -> List[float]:
    """Child seconds of each open span on this thread, innermost last."""
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def _add(name: str, total: float, child: float) -> None:
    stack = _stack()
    if stack:
        stack[-1] += total
    spans = _table().spans
    rec = spans.get(name)
    if rec is None:
        spans[name] = [1, total, total - child]
    else:
        rec[0] += 1
        rec[1] += total
        rec[2] += total - child


class span:
    """``with span(name):`` — a trace annotation and a table entry while
    a profile is taken, next to nothing otherwise."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def __enter__(self) -> "span":
        global _stale, _annotation
        if not _on():
            _stale = True
            return self
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        _stack().append(0.0)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is None:
            return
        total = _clock() - self._t0
        self._ann.__exit__(*exc)
        self._ann = None
        _add(self.name, total, _stack().pop())


def traced(name: str):
    """Decorator: the whole call is one ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return inner
    return wrap


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while a profile is taken."""
    global _stale
    if not _on():
        _stale = True
        return
    c = _table().counters
    c[name] = c.get(name, 0) + n


def clock() -> Optional[float]:
    """The start of an ``interval``: the host clock while a profile is
    taken, else None."""
    return _clock() if _on() else None


def interval(name: str, seconds: float) -> None:
    """Record a span of ``seconds`` that lay inside this thread's open
    span, though another thread saw where it ended."""
    _add(name, seconds, 0.0)


def snapshot() -> Dict[str, Dict]:
    """``{"spans": {name: {count, total_s, self_s}}, "counters": {name:
    n}}`` of the newest profile, merged over threads."""
    global _stale
    if not _on():
        _stale = True
    with _lock:
        tables = list(_tables)
    spans: Dict[str, Dict] = {}
    counters: Dict[str, int] = {}
    for t in tables:
        for name, (n, total, own) in list(t.spans.items()):
            s = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0})
            s["count"] += n
            s["total_s"] += total
            s["self_s"] += own
        for name, n in list(t.counters.items()):
            counters[name] = counters.get(name, 0) + n
    return {"spans": spans, "counters": counters}
