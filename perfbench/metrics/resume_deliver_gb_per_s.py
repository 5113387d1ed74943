"""Read layer, deliver: bytes delivered to the consumers (each piece copied
from the session arena into its leaf's buffer) over the summed self time of
the ``ckio.deliver`` spans, in GB/s.

From the program's own spans (``repro.core.tracing``), which record while
the profiler traces. Read only where the trace holds the device's
operations (a run on the chip), beside which the spans are put; None where
the program has no such span."""


def read(r):
    if r.trace is None:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    snap = tracing.snapshot()
    seconds = snap.self_s("ckio.deliver")
    if seconds <= 0:
        return None
    delivered = sum(s.ids["nbytes"] for s in snap.named("ckio.deliver"))
    return delivered / seconds / 1e9
