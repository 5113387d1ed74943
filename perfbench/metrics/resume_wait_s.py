"""Read layer, consumer wait: self time per resume of the ``ckio.wait``
spans (``TaskScheduler.run_until``) less the tasks run inside them (the
deliveries, the session's start and close): the consumer thread parked with
nothing to do, in s.

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
    snap = tracing.snapshot()
    if not snap.named("ckio.wait"):
        return None
    return snap.self_s("ckio.wait") / r.counters["resumes"]
