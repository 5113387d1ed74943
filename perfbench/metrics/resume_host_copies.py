"""Read layer, copies: host copies of each checkpoint byte in a resume,
(``ckio.bytes_copied`` + ``checkpoint.unpack_bytes``) over the checkpoint's
bytes times the resumes traced.

From the program's own counters (``repro.core.tracing``), which count
while the profiler traces. Read only where the trace holds the device's
operations (a run on the chip); None where the program has no such
counter."""


def read(r):
    if (r.trace is None or not r.counters.get("resumes")
            or not r.counters.get("checkpoint_bytes")):
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    c = tracing.snapshot().counters
    if "checkpoint.unpack_bytes" not in c:
        return None
    copied = c.get("ckio.bytes_copied", 0) + c["checkpoint.unpack_bytes"]
    return copied / (r.counters["checkpoint_bytes"] * r.counters["resumes"])
