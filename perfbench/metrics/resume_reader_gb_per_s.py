"""Read layer, readers: checkpoint bytes over the readers' wall time, from
the start of a session's first ``ckio.reader.read`` span to the end of its
last, summed over the traced sessions, in GB/s.

From the program's own spans (``repro.core.tracing``), which record while
the profiler traces. Read only where the trace holds the device's
operations (a run on the chip), beside which the spans are put; None where
the program has no such span."""


def read(r):
    if r.trace is None or not r.counters.get("checkpoint_bytes"):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    walls = {}
    for s in tracing.snapshot().named("ckio.reader.read"):
        lo, hi = walls.get(s.ids["session"], (s.start, s.end))
        walls[s.ids["session"]] = (min(lo, s.start), max(hi, s.end))
    ns = sum(hi - lo for lo, hi in walls.values())
    if ns <= 0:
        return None
    return r.counters["checkpoint_bytes"] * len(walls) / ns
