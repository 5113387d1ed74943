#!/usr/bin/env bash
# CI entry point: tier-1 test suite + benchmark smokes + coverage floor.
#
# Usage: scripts/ci.sh            (from the repo root)
#
# Tier-1 (must stay green; see ROADMAP.md):
#   PYTHONPATH=src python -m pytest -x -q
# Smokes (quick mode writes scratch-dir BENCH_*.quick.json files; the
# committed repo-root BENCH_*.json artifacts are full-mode only and are
# NOT touched by CI — regenerate them by running the benchmarks without
# --quick):
#   benchmarks/perf_hotpath.py --quick       zero-copy session drain
#   benchmarks/perf_device_ingest.py --quick device-ingest path (incl. the
#                                            Pallas interpret-mode kernel
#                                            check)
#   benchmarks/perf_streaming.py --quick     event-driven splinter streaming
#                                            (overlap fraction + streamed/
#                                            whole-window bit-equality)
#   benchmarks/perf_numa.py --quick          topology-aware placement
#                                            (cross-domain delivery bytes
#                                            drop, zero-copy + bit-identity
#                                            preserved)
#   benchmarks/perf_shm.py --quick           multi-process reader backend
#                                            (shm arena drain >= 1.2x the
#                                            copy-through-pipe baseline,
#                                            consumer bytes_copied == 0,
#                                            process/thread bit-identity)
#   benchmarks/perf_recovery.py --quick      fault recovery (worker SIGKILLed
#                                            mid-drain completes bit-
#                                            identically via respawn AND
#                                            re-issue, overhead <= 1.5x a
#                                            clean paced drain)
#   benchmarks/perf_fileset.py --quick       multi-shard FileSet sessions
#                                            (sharded drain bit-identical to
#                                            the single-file stream, 8-device
#                                            staged-bytes ledger: constructor
#                                            sharding stages 1x the window
#                                            balanced across devices, legacy
#                                            per-call fallback ~2x)
#   benchmarks/perf_service.py --quick       persistent reader service
#                                            (pooled re-arm steady-state
#                                            setup >= 5x per-session spawn,
#                                            arena recycling, >= 4 concurrent
#                                            sessions through one pool,
#                                            bit-identical + zero-copy,
#                                            /dev/shm clean after shutdown)
#   benchmarks/perf_serve.py --quick         continuous-batching serve under
#                                            Poisson session churn (goodput
#                                            >= 1.5x the static baseline at
#                                            equal-or-better e2e p99, bit-
#                                            identical to the sequential
#                                            oracle, zero-copy ingest,
#                                            ServiceBusy backpressure on the
#                                            measured path, /dev/shm clean)
#   benchmarks/perf_coldpath.py --quick      cold-cache read engine (depth-
#                                            managed async submission >= 1.5x
#                                            blocking under the modeled PFS,
#                                            O_DIRECT end-to-end, QueueTuner
#                                            within 10% of the fixed grid
#                                            best, mincore-verified eviction
#                                            state stamped in the artifact;
#                                            hosts without eviction still run
#                                            — local legs record warm)
# Bench legs run under scripts/env.sh (tcmalloc LD_PRELOAD + quiet XLA env
# when available; silent degrade otherwise).
# Fault matrix: the seeded fault-injection tests replayed under several
# CKIO_FAULT_SEED values (tier-1 already runs the full recovery suite once
# under the default seed; the matrix re-derives the FaultPlan from each
# seed and must stay deterministic + green for all of them).
# Coverage floor: line coverage of src/repro/core + src/repro/data +
# src/repro/io + src/repro/ipc + src/repro/serve over the core/data-focused
# tests must stay >= the floor in scripts/coverage_floor.py (stdlib settrace
# fallback — no third-party deps required).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 tests =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

# Bench legs run under the tuned environment (tcmalloc preload + quiet
# XLA logging when the host has them; scripts/env.sh degrades silently).
source scripts/env.sh

echo "== hot-path benchmark (smoke) =="
python benchmarks/perf_hotpath.py --quick

echo "== device-ingest benchmark (smoke, interpret check) =="
python benchmarks/perf_device_ingest.py --quick

echo "== streaming benchmark (smoke, overlap + equivalence) =="
python benchmarks/perf_streaming.py --quick

echo "== numa benchmark (smoke, cross-domain locality + equivalence) =="
python benchmarks/perf_numa.py --quick

echo "== shm / multi-process backend benchmark (smoke) =="
python benchmarks/perf_shm.py --quick

echo "== recovery benchmark (smoke, mid-drain SIGKILL) =="
python benchmarks/perf_recovery.py --quick

echo "== fileset benchmark (smoke, sharded sessions + staged-bytes ledger) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/perf_fileset.py --quick

echo "== reader-service benchmark (smoke, pooled re-arm vs spawn) =="
python benchmarks/perf_service.py --quick

echo "== serve benchmark (smoke, continuous batching under churn) =="
python benchmarks/perf_serve.py --quick

echo "== cold-path benchmark (smoke, depth-managed submission + O_DIRECT) =="
python benchmarks/perf_coldpath.py --quick

echo "== fault matrix (seeded deterministic replay) =="
for seed in 11 20260809 424242; do
  echo "-- CKIO_FAULT_SEED=$seed --"
  CKIO_FAULT_SEED=$seed PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -q tests/test_recovery.py \
    -k "fault_plan or replay or reissue or respawn"
done

echo "== fault matrix (pooled reader-service backend) =="
for seed in 11 20260809 424242; do
  echo "-- CKIO_FAULT_SEED=$seed (service) --"
  CKIO_FAULT_SEED=$seed PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -q tests/test_service.py \
    -k "fault_plan or respawn or sibling"
done

echo "== coverage floor (core + data + io + ipc + serve) =="
python scripts/coverage_floor.py

echo "== ci OK =="
