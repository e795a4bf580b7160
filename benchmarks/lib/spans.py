"""Host spans around the benchmark's calls into the program.

Kept in memory on ``time.perf_counter``; each span is also a
``jax.profiler.TraceAnnotation`` under ``TRACE_PREFIX``, so that in a traced
run the same spans lie on the profiler's clock beside the device's
operations and an idle gap can be named by what the host was doing.
"""

from __future__ import annotations

import contextlib
import time

TRACE_PREFIX = "bench:"


class Spans:
    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []   # name, start, end

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(TRACE_PREFIX + name):
            start = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, start, time.perf_counter()))

    def durations(self, start: float, end: float) -> dict[str, list[float]]:
        """Seconds of every span that lies inside [start, end], by name."""
        out: dict[str, list[float]] = {}
        for name, t0, t1 in self.records:
            if t0 >= start and t1 <= end:
                out.setdefault(name, []).append(t1 - t0)
        return out
