"""The controls' broken guarantee: a device whose writes sit in a
volatile cache that the kill throws away. Each cell's durability
guarantee (a save is durable when it returns; an fsync'd append
survives a cold remount) then fails, and the comparison has to say so.
Only the control and the tests use this."""

from __future__ import annotations

import threading
from typing import Dict


class VolatileWrites:
    """Record the old contents of every block written to ``dev`` while
    armed; ``lose()`` puts them back, as if those writes never left a
    write cache that lost power."""

    def __init__(self, dev):
        self.dev = dev
        self._old: Dict[int, bytes] = {}
        self._lock = threading.Lock()
        self._write = None

    def arm(self) -> None:
        write = self._write = self.dev.write_block

        def volatile(blockno, data):
            with self._lock:
                if blockno not in self._old:
                    self._old[blockno] = self.dev.read_block(blockno)
            write(blockno, data)

        self.dev.write_block = volatile

    def lose(self) -> int:
        """Undo every write since ``arm``; returns the blocks undone."""
        if self._write is not None:
            self.dev.write_block = self._write
            self._write = None
        with self._lock:
            old, self._old = self._old, {}
        for blockno, data in old.items():
            self.dev.write_block(blockno, data)
        return len(old)
