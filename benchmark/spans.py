"""The benchmark's own spans: host clock, and — while the profiler runs —
the same intervals on the device's clock.

Every span is also a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``,
so in a traced run it lands in the profiler's trace beside the device's
operations and ``trace_reduce`` can say what the benchmark was doing in an
idle gap. Outside a trace the annotation costs a flag test.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self) -> None:
        #: (name, start, end) on ``time.perf_counter``'s clock, in order
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def walls(self, name: str, since: float = 0.0) -> list[float]:
        """Seconds of every span of that name that started at or after
        ``since``."""
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= since]

    def total(self, name: str, since: float = 0.0) -> float:
        return sum(self.walls(name, since))
