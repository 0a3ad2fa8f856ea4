"""Host spans of the benchmark's own: recorded in memory on the host clock,
and written into the profiler's trace (when one is running) under
`bench/<name>`, so that a device idle gap can be attributed to what the host
was doing. Each span the readers use ends synchronised with the device (the
calls it wraps return host values)."""

from __future__ import annotations

import time
from contextlib import contextmanager

PREFIX = "bench/"


class Spans:
    def __init__(self):
        self.events: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        import jax.profiler

        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.monotonic()
            try:
                yield
            finally:
                self.events.append((name, t0, time.monotonic()))

    def within(self, name: str, t_lo: float, t_hi: float) -> list[float]:
        """Durations of the spans of this name that ended inside (t_lo, t_hi]."""
        return [t1 - t0 for n, t0, t1 in self.events
                if n == name and t_lo < t1 <= t_hi]
