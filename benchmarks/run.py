"""Benchmark runner — one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows (and saves them under
benchmarks/results/bench.csv). Sizes scale with CKIO_BENCH_MB /
CKIO_BENCH_QUICK (quick defaults sized for this 1-core container).
"""
from __future__ import annotations

import csv
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common


def fig1_naive_overdecomposition() -> None:
    from benchmarks import fig1_naive_overdecomposition as m
    m.run()


def fig2_disk_vs_network() -> None:
    from benchmarks import fig2_disk_vs_network as m
    m.run()


def fig4_ckio_vs_naive() -> None:
    from benchmarks import fig4_ckio_vs_naive as m
    m.run()


def fig7_collective_baseline() -> None:
    from benchmarks import fig7_collective_baseline as m
    m.run()


def fig8_9_overlap() -> None:
    from benchmarks import fig8_9_overlap as m
    m.run()


def fig12_migration() -> None:
    from benchmarks import fig12_migration as m
    m.run()


def fig13_train_input() -> None:
    from benchmarks import fig13_train_input as m
    m.run()


def sec5_breakdown() -> None:
    from benchmarks import sec5_breakdown as m
    m.run()


def perf_input_hillclimb() -> None:
    from benchmarks import perf_input_hillclimb as m
    m.run()


def perf_hotpath() -> None:
    # Writes BENCH_hotpath.json at the repo root (before/after hot-path
    # numbers tracked across PRs).
    from benchmarks import perf_hotpath as m
    m.run(quick=common.QUICK)


def perf_device_ingest() -> None:
    # Writes BENCH_device_ingest.json at the repo root (host-path vs
    # device-ingest per-step numbers + host-permutation-bytes proof).
    from benchmarks import perf_device_ingest as m
    m.run(quick=common.QUICK)


def perf_streaming() -> None:
    # Writes BENCH_streaming.json at the repo root (whole-window vs
    # event-driven streamed staging: overlap fraction, stage latency,
    # in-flight high-water mark, bit-identical batches).
    from benchmarks import perf_streaming as m
    m.run(quick=common.QUICK)


def perf_numa() -> None:
    # Writes BENCH_numa.json at the repo root (cross-domain delivery bytes
    # under a skewed-consumer layout: locality-blind vs topology-aware
    # placement, zero-copy + streamed bit-identity preserved).
    from benchmarks import perf_numa as m
    m.run(quick=common.QUICK)


def perf_shm() -> None:
    # Writes BENCH_shm.json at the repo root (multi-process reader backend:
    # shared-memory arena drain vs copy-through-pipe baseline, consumer-side
    # bytes_copied == 0, process/thread bit-identity).
    from benchmarks import perf_shm as m
    m.run(quick=common.QUICK)


def perf_recovery() -> None:
    # Writes BENCH_recovery.json at the repo root (fault recovery: a worker
    # SIGKILLed mid-drain vs a clean paced drain — respawn/re-issue both
    # complete bit-identically with bytes_copied == 0, overhead bounded).
    from benchmarks import perf_recovery as m
    m.run(quick=common.QUICK)


def perf_service() -> None:
    # Writes BENCH_service.json at the repo root (persistent reader
    # service: K back-to-back sessions on pooled re-armed workers vs
    # per-session spawn — steady-state setup >= 5x faster, bit-identical,
    # bytes_copied == 0, arena recycling, >= 4 concurrent sessions through
    # one pool, /dev/shm clean after shutdown).
    from benchmarks import perf_service as m
    m.run(quick=common.QUICK)


def perf_fileset() -> None:
    # Writes BENCH_fileset.json at the repo root (multi-shard FileSet drain
    # vs the same stream as one file — bit-identical, zero-copy — plus the
    # 8-device sharded staged-bytes ledger: constructor sharding stages 1x
    # the window, balanced across devices; the legacy per-call fallback
    # pays ~2x). On the CPU it needs the 8-device host mesh from
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 set before start.
    from benchmarks import perf_fileset as m
    m.run(quick=common.QUICK)


def perf_serve() -> None:
    # Writes BENCH_serve.json at the repo root (continuous-batching serve
    # under Poisson session churn: goodput >= 1.5x the static baseline at
    # equal-or-better e2e p99, bit-identical to the sequential oracle,
    # zero-copy prompt ingest, ServiceBusy backpressure on the measured
    # path with zero admitted requests dropped, /dev/shm clean).
    from benchmarks import perf_serve as m
    m.run(quick=common.QUICK)


def perf_coldpath() -> None:
    # Writes BENCH_coldpath.json at the repo root (cold-cache read engine:
    # blocking preadv vs depth-managed async submission vs O_DIRECT —
    # >= 1.5x under the modeled PFS, bit-identical, zero-copy, QueueTuner
    # within 10% of the fixed grid best, mincore-verified eviction state).
    from benchmarks import perf_coldpath as m
    m.run(quick=common.QUICK)


ALL = [
    fig1_naive_overdecomposition,
    fig2_disk_vs_network,
    fig4_ckio_vs_naive,
    fig7_collective_baseline,
    fig8_9_overlap,
    fig12_migration,
    fig13_train_input,
    sec5_breakdown,
    perf_input_hillclimb,
    perf_hotpath,
    perf_device_ingest,
    perf_streaming,
    perf_numa,
    perf_shm,
    perf_recovery,
    perf_service,
    perf_serve,
    perf_fileset,
    perf_coldpath,
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for fn in ALL:
        if only and only not in fn.__name__:
            continue
        t0 = time.time()
        print(f"# --- {fn.__name__} ---", flush=True)
        try:
            fn()
        except Exception as e:  # keep the suite running
            common.emit(f"{fn.__name__}_ERROR", 0.0, repr(e)[:120])
        print(f"# {fn.__name__}: {time.time()-t0:.1f}s", flush=True)

    os.makedirs("benchmarks/results", exist_ok=True)
    with open("benchmarks/results/bench.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["name", "us_per_call", "derived"],
                           extrasaction="ignore")
        w.writeheader()
        for row in common.rows():
            w.writerow(row)


if __name__ == "__main__":
    main()
