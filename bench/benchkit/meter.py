"""Host-side meters the harness puts around the program's own calls.

``HashMeter`` wraps one mount's ``KernelServices.checksum`` and
``checksum_batch`` instance attributes. It counts the bytes the file
system asks to have hashed and the wall time until the answer is back,
whatever implements the hash (the Pallas kernel on a TPU, zlib's CRC on
the host), so a hash share computed from it counts the same work on
either side of a change of implementation.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional


@contextlib.contextmanager
def span(name: str):
    """A harness span: a ``TraceAnnotation`` in the profiler's trace, so
    the traced run can charge the device's idle gaps to what the host was
    doing. It costs next to nothing while no trace is being taken."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class HashMeter:
    """Bytes hashed and seconds spent in the services' hash calls, summed
    per phase (``phase`` is set by the generator; calls outside any phase
    are not counted)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.phase: Optional[str] = None
        self.totals: Dict[str, Dict[str, float]] = {}

    def _add(self, nbytes: int, seconds: float) -> None:
        phase = self.phase
        if phase is None:
            return
        with self._lock:
            t = self.totals.setdefault(phase, {"bytes": 0, "seconds": 0.0,
                                               "calls": 0})
            t["bytes"] += nbytes
            t["seconds"] += seconds
            t["calls"] += 1

    def attach(self, ks) -> None:
        one, many = ks.checksum, ks.checksum_batch

        def checksum(data):
            t0 = time.perf_counter()
            out = one(data)
            self._add(len(data), time.perf_counter() - t0)
            return out

        def checksum_batch(blocks):
            blocks = list(blocks)
            t0 = time.perf_counter()
            out = many(blocks)
            self._add(sum(len(b) for b in blocks), time.perf_counter() - t0)
            return out

        ks.checksum = checksum
        ks.checksum_batch = checksum_batch

    def reset(self) -> None:
        with self._lock:
            self.totals = {}
