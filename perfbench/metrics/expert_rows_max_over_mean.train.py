"""Route layer: how unevenly the router loads the held experts. Per MoE
layer and microbatch, the rows of the busiest held expert over the mean
rows of the held experts, averaged over the traced layers and
microbatches: the program's ``moe.expert_rows_max`` (summed busiest rows)
and ``moe.expert_row_groups`` (how many such groups) against
``moe.routed_rows`` (all rows the held experts computed).

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
    c = tracing.snapshot().counters
    rows, busiest = c.get("moe.routed_rows"), c.get("moe.expert_rows_max")
    if not rows or busiest is None or not c.get("moe.expert_row_groups"):
        return None
    return busiest * r.counters["experts_held"] / rows
