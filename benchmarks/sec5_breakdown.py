"""Paper §V: CkIO execution-time breakdown — I/O vs data permutation vs
over-decomposition overhead, at a high over-decomposition factor.

The permutation is the summed time of the ``ckio.deliver`` spans (each
piece copied from the session arena into its consumer's buffer), recorded
under a profiler trace taken around the read."""
from __future__ import annotations

import shutil
import tempfile

from benchmarks.ckio_read import ckio_read
from benchmarks.common import BASE_MB, QUICK, emit, ensure_file, cold


def run() -> None:
    import jax

    from repro.core import tracing

    mb = BASE_MB
    path = ensure_file("sec5", mb)
    clients = 512
    readers = 8
    cold(path)
    trace_dir = tempfile.mkdtemp(prefix="ckio-sec5-")
    tracing.reset()
    jax.profiler.start_trace(trace_dir)
    try:
        nbytes, m = ckio_read(path, clients, readers, num_pes=8)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    io_s = m["ingest_s"]
    permute_s = tracing.snapshot().total_s("ckio.deliver")
    emit("sec5_io", io_s * 1e6, f"{m['throughput_MBps']:.0f}MBps")
    emit("sec5_permutation", permute_s * 1e6,
         f"{100*permute_s/max(io_s,1e-9):.1f}%_of_io")
    emit("sec5_requests", m["requests"],
         f"pieces={int(m['pieces_served'])}_steals={int(m['steals'])}")
    emit("sec5_imbalance", 0.0, f"max/mean={m['imbalance']:.3f}")


if __name__ == "__main__":
    run()
