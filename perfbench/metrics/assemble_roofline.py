"""Assemble layer: the batch assembly's share of its HBM roofline, in %.

The least time is the bytes the assembly must move (the staged window in,
inputs and labels out, from the shapes: ``harness.flops.assemble_bytes``)
over the chip's HBM bandwidth; the time is that of every device operation
of the ingest executables in the trace, whatever implements them."""

INGEST_MODULES = ("reassemble", "device_ingest")


def read(r):
    if r.trace is None or not r.counters.get("steps"):
        return None
    seconds = r.trace.module_seconds(INGEST_MODULES)
    if seconds <= 0:
        return None
    least = (r.counters["assemble_bytes_per_step"] * r.counters["steps"]
             / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
