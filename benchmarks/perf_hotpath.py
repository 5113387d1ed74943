"""Hot-path microbenchmark: warm-cache session-drain throughput + per-task
dispatch cost, before/after the zero-copy rework.

"Before" reproduces the seed delivery path faithfully on today's code:
  * ``use_preadv=False`` — the seed's ``os.pread`` allocate-then-copy into
    the arena (copy #1 + transient bytes alloc);
  * destination-buffer reads — per-piece memcpy arena→client buffer (copy #2);
  * ``prefault_arena=True`` — the seed's ``bytearray`` arena zero-filled the
    whole session on the start critical path.

"After" is the new path: ``preadv`` straight into the arena (zero
intermediate copies), borrowed-view delivery (zero delivery copies, proven
via ``bytes_copied == 0``), coalesced pieces.

Warm cache on purpose: with the file in DRAM the storage cost vanishes and
the measured number is exactly the per-byte software overhead this PR
attacks. Writes ``BENCH_hotpath.json`` at the repo root.

Usage: python benchmarks/perf_hotpath.py [--quick] [--mb N]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common
from repro.core import CkIO, FileOptions
from repro.core.scheduler import TaskScheduler

NUM_PES = 8
NUM_READERS = 4
SPLINTER = 8 << 20


def drain_session(path: str, *, legacy: bool, num_clients: int = 64,
                  timeout: float = 600.0):
    """One full session drain; returns (wall_s, nbytes, metrics_summary)."""
    ck = CkIO(num_pes=NUM_PES, pes_per_node=NUM_PES)     # one node: coalesced
    opts = FileOptions(
        num_readers=NUM_READERS,
        splinter_bytes=SPLINTER,
        prefault_arena=legacy,        # seed zero-filled the arena up front
    )
    fh = ck.open_sync(path, opts)
    if legacy:
        fh.posix.use_preadv = False                       # seed read path
    size = fh.size
    t0 = time.perf_counter()
    sess = ck.start_read_session_sync(fh, size, 0)
    per = size // num_clients
    futs = []
    for i in range(num_clients):
        off = i * per
        n = per if i < num_clients - 1 else size - off
        c = ck.make_client(pe=i % NUM_PES)
        if legacy:
            futs.append(ck.read_future(sess, n, off, client=c))   # dest copy
        else:
            futs.append(ck.read_view_future(sess, n, off, client=c))
    got = 0
    for f in futs:
        got += f.wait(ck.sched, timeout=timeout).nbytes
    wall = time.perf_counter() - t0
    assert got == size
    summary = sess.metrics.summary()
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    return wall, size, summary


def bench_drain(path: str, *, legacy: bool, trials: int = 3):
    drain_session(path, legacy=legacy)                    # warm cache + JIT-ish
    results = []
    for _ in range(trials):
        wall, nbytes, summary = drain_session(path, legacy=legacy)
        results.append((wall, nbytes, summary))
    best = min(results, key=lambda r: r[0])
    wall, nbytes, summary = best
    return {
        "wall_s": round(wall, 4),
        "MBps": round(nbytes / wall / 1e6, 1),
        "bytes": nbytes,
        "bytes_copied": int(summary["bytes_copied"]),
        "pieces_served": int(summary["pieces_served"]),
        "trials": trials,
    }


def bench_dispatch(num_pes: int = 512, ntasks: int = 20000):
    """Per-task scheduler cost with many (mostly idle) PEs — the case the
    O(1) ready-deque targets — plus the batched-enqueue variant."""
    s = TaskScheduler(num_pes=num_pes)
    sink = []
    t0 = time.perf_counter()
    for i in range(ntasks):
        s.enqueue(i % num_pes, sink.append, None)
    s.pump()
    per_task = time.perf_counter() - t0
    assert len(sink) == ntasks

    s2 = TaskScheduler(num_pes=num_pes)
    sink2 = []
    t0 = time.perf_counter()
    s2.enqueue_many((i % num_pes, sink2.append, (None,)) for i in range(ntasks))
    s2.pump()
    per_task_batched = time.perf_counter() - t0
    assert len(sink2) == ntasks
    return {
        "num_pes": num_pes,
        "ntasks": ntasks,
        "us_per_task": round(per_task / ntasks * 1e6, 3),
        "us_per_task_batched": round(per_task_batched / ntasks * 1e6, 3),
    }


def run(quick: bool = False, mb: int = 0) -> dict:
    mb = mb or int(os.environ.get(
        "CKIO_HOTPATH_MB", "32" if quick else "256"))
    if mb <= 0:
        raise SystemExit(f"--mb must be positive, got {mb}")
    path = common.ensure_file("hotpath", mb)

    before = bench_drain(path, legacy=True, trials=2 if quick else 3)
    after = bench_drain(path, legacy=False, trials=2 if quick else 3)
    dispatch = bench_dispatch(ntasks=5000 if quick else 20000)

    speedup = after["MBps"] / before["MBps"] if before["MBps"] else 0.0
    report = {
        "bench": "perf_hotpath",
        "file_mb": mb,
        "warm_cache": True,
        "num_pes": NUM_PES,
        "num_readers": NUM_READERS,
        "splinter_bytes": SPLINTER,
        "before_seed_path": before,       # pread+copy, dest-copy delivery, timed
        "after_zero_copy": after,         # preadv into arena, borrowed views
        "speedup": round(speedup, 2),
        "dispatch": dispatch,
    }
    common.emit("hotpath_before_MBps", before["wall_s"] * 1e6,
                f"{before['MBps']:.0f}MBps")
    common.emit("hotpath_after_MBps", after["wall_s"] * 1e6,
                f"{after['MBps']:.0f}MBps")
    common.emit("hotpath_speedup", 0.0, f"{speedup:.2f}x")
    common.emit("hotpath_bytes_copied_view_path", 0.0,
                str(after["bytes_copied"]))
    common.emit("hotpath_dispatch", dispatch["us_per_task"],
                f"batched={dispatch['us_per_task_batched']}us")
    common.write_report("hotpath", report, quick)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small file / fewer trials (CI smoke)")
    ap.add_argument("--mb", type=int, default=0,
                    help="file size in MB (default 256, quick 32)")
    args = ap.parse_args()
    report = run(quick=args.quick, mb=args.mb)
    ok = (report["speedup"] >= 1.5
          and report["after_zero_copy"]["bytes_copied"] == 0)
    print(f"# speedup={report['speedup']}x "
          f"bytes_copied={report['after_zero_copy']['bytes_copied']} "
          f"{'OK' if ok else 'BELOW-TARGET'}")


if __name__ == "__main__":
    main()
