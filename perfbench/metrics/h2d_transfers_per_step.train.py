"""Stage layer: host-to-device transfers of input bytes per traced step,
the program's ``pipeline.h2d_transfers`` counter (one per ``device_put``,
counted where ``IngestMetrics`` counts them) over the steps.

From the program's own counters (``repro.core.tracing``), which count
while the profiler traces. Read only where the trace holds the device's
operations (a run on the chip); None where the program has no such
counter."""


def read(r):
    if r.trace is None or not r.counters.get("steps"):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    n = tracing.snapshot().counters.get("pipeline.h2d_transfers")
    if n is None:
        return None
    return n / r.counters["steps"]
