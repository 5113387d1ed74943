"""Host spans around the calls into the program's layers.

Each span is timed on the host clock and, while a trace is being taken,
also written into the profiler's trace as a ``TraceAnnotation`` of the same
name, so that idle gaps on the device can be put down to what the host was
doing."""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List


class Spans:
    def __init__(self):
        self.tracing = False
        self.durations: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax.profiler

            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                yield
                dt = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            yield
            dt = time.perf_counter() - t0
        self.durations.setdefault(name, []).append(dt)

    def mean(self, name: str):
        d = self.durations.get(name)
        return sum(d) / len(d) if d else None

    def clear(self) -> None:
        self.durations.clear()
