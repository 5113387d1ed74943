"""Model step layer: model FLOPs utilization of the traced window, in %:
model FLOPs per step (PaLM convention, ``harness.flops``) times the steps
traced, over the traced window, over the chips' bf16 peak."""


def read(r):
    if r.trace is None or not r.counters.get("steps"):
        return None
    flops = r.counters["flops_per_step"] * r.counters["steps"]
    return 100.0 * flops / r.trace.window_s / (
        r.chips * r.peaks["bf16_flops_per_s"])
