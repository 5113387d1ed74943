"""Experts layer: the held experts' grouped matmul's share of its bf16
roofline, in %.

The least time is the FLOPs the held experts' three matmuls must do for the
rows routed to them, forward and both backward products (the program's
``moe.routed_rows`` counter over the traced steps times
``harness.flops_moe.expert_flops_per_row``), over the chips' bf16 peak; the
time is the device self time of the grouped-matmul operations in the
traced window, found by their stable name (``ragged-dot``: the program's
``jax.lax.ragged_dot`` and its group metadata), whatever implements them.
Recomputed forward products take time and add no FLOPs here.

From the program's own counters (``repro.core.tracing``), which count
while the profiler traces. Read only where the trace holds the device's
operations (a run on the chip); None where the program has no such counter
or the trace no such operation."""

GMM_OPS = "ragged-dot"


def read(r):
    if r.trace is None or not r.counters.get("steps"):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    rows = tracing.snapshot().counters.get("moe.routed_rows")
    if not rows:
        return None
    seconds = sum(s for name, s in r.trace.op_seconds().items()
                  if GMM_OPS in name.split("/", 1)[-1])
    if seconds <= 0:
        return None
    least = (rows * r.counters["expert_flops_per_row"]
             / (r.chips * r.peaks["bf16_flops_per_s"]))
    return 100.0 * least / seconds
