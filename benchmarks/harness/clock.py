"""The benchmark's own clock: set-up stages and host spans, kept in memory.

Spans are recorded from the benchmark's files, around the calls into the
program. While the profiler runs each span is also written into the
profiler's trace (`bench:<name>`), so the device's idle gaps and the host
spans sit on one clock.
"""
from __future__ import annotations

import contextlib
import time

now = time.perf_counter
SPAN_PREFIX = "bench:"


class Spans:
    def __init__(self) -> None:
        self.events: list = []          # (name, t0, t1) on `now`'s clock
        self.annotate = False           # True while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str):
        note = contextlib.nullcontext()
        if self.annotate:
            import jax
            note = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        t0 = now()
        try:
            with note:
                yield
        finally:
            self.events.append((name, t0, now()))

    def seconds(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.events if n == name]
