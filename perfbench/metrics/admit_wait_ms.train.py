"""Admit layer: mean time per step of ``service.admit``, from a read
session's submission to the reader service until every worker checked out
for it has attached, in ms.

From the program's own spans (``repro.core.tracing``), which record while
the profiler traces. Read only where the trace holds the device's
operations (a run on the chip), beside which the spans are put; None where
the program has no such span."""


def read(r):
    if r.trace is None or not r.counters.get("steps"):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot().named("service.admit")
    if not spans:
        return None
    return sum(s.ns for s in spans) / 1e6 / r.counters["steps"]
