"""A wall clock that the serving handler can run on.

``ClientHandler`` only runs on a ``VirtualClock``.  This one keeps the
event queue but reads the host's clock: ``now()`` is wall seconds since
``start()``, and ``advance_to(t)`` waits until the wall clock reaches
``t`` before it fires the events due by then.  Every stamp the handler
writes is then a wall time, and a modeled delay becomes a real wait.
"""
from __future__ import annotations

import contextlib
import time

from repro.core.clock import VirtualClock


class WallClock(VirtualClock):

    def __init__(self, span=None):
        super().__init__(0.0)
        self._t0 = None
        # span(name) -> context manager around each wait (trace annotation)
        self._span = span or (lambda name: contextlib.nullcontext())
        self.waits = []                 # (start, end) of each wait

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def now(self) -> float:
        if self._t0 is None:
            return self._now
        return time.perf_counter() - self._t0

    def advance_to(self, t: float) -> None:
        t0 = self.now()
        if t > t0:
            with self._span("clock_wait"):
                time.sleep(t - t0)
            self.waits.append((t0, self.now()))
        super().advance_to(max(t, self._now))
