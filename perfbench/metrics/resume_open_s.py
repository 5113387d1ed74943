"""Read layer, session: mean host time per resume of ``ckio.session.start``
(the session planned, its arena allocated, its readers started), in s.

From the program's own spans (``repro.core.tracing``), which record while
the profiler traces. Read only where the trace holds the device's
operations (a run on the chip), beside which the spans are put; None where
the program has no such span."""


def read(r):
    if r.trace is None or not r.counters.get("resumes"):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot().named("ckio.session.start")
    if not spans:
        return None
    return sum(s.ns for s in spans) / 1e9 / r.counters["resumes"]
