"""FileSet benchmark: multi-shard corpora and the sharded staged-bytes proof.

Part A — **shard transparency**: the same token stream served as one file
and as an N-shard :class:`FileSet` (uneven shard sizes, so stripe bounds
land at arbitrary window positions). Whole-window host drains of both must
be bit-identical with ``bytes_copied == 0`` on each session (borrowed-view
delivery survives the ``ShardedFile`` segment table); the per-step wall
ratio is the FileSet manifest's overhead on a read-bound drain, and
``ShardMetrics.shard_bytes`` must account for every physical byte per shard.

Part B — **sharded staged-bytes accounting**, on a mesh of every device
this process holds: 8 host devices on the CPU, which the command line asks
for before JAX starts (``XLA_FLAGS=--xla_force_host_platform_device_count=8``),
or the chips present on an accelerator. With fewer, the script refuses and
says what it found. A streaming
pipeline built with ``sharding=`` (constructor) places every splinter chunk
against the device spans as its read lands: total staged bytes == 1x the
window per step, per-device max == window/ndev, zero cross-host
placements, zero ``RuntimeWarning``s, ``host_permute_bytes == 0``, and the
assembled global array is bit-identical to the single-file host reference.
The legacy per-call ``get_batch_device(sharding=...)`` on the same
workload — the gap this PR closes — warns once and stages ~2x the window
every step (streamed chunks placed-then-discarded, plus the whole-window
restage); the report records both ledgers side by side.

Writes ``BENCH_fileset.json`` at the repo root (full mode).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python benchmarks/perf_fileset.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks import common
from repro.core import FileOptions
from repro.data import CkIOPipeline, FileSet, make_token_file
from repro.data.fileset import write_token_shards
from repro.data.tokenfile import HEADER_BYTES

HOST_DEVICES = 8       # the CPU run's virtual devices
NUM_PES = 4
NUM_READERS = 4
WARM_STEPS = 1
# Deliberately uneven shard weights: shard boundaries must fall at
# arbitrary offsets inside step windows, not on window edges.
SHARD_WEIGHTS = (5, 2, 7, 3, 6, 4)


def workload(quick: bool):
    if quick:
        # 256 KiB window (64 x 1024 tokens), 4 shards
        return dict(steps=4, global_batch=64, seq_len=1023,
                    splinter_bytes=32 * 1024, num_shards=4)
    # 1 MiB window (128 x 2048 tokens), 6 shards
    return dict(steps=12, global_batch=128, seq_len=2047,
                splinter_bytes=128 * 1024, num_shards=6)


def build_corpus(wl: dict):
    """One token stream, twice: a single file and an uneven shard split."""
    ntok = (wl["steps"] + WARM_STEPS + 1) * \
        wl["global_batch"] * (wl["seq_len"] + 1) + 64
    tag = f"{wl['global_batch']}x{wl['seq_len']}x{wl['steps']}"
    single = os.path.join(common.BENCH_DIR, f"fileset_single_{tag}.bin")
    if not os.path.exists(single):
        make_token_file(single, ntok, vocab_size=32000, seed=29)
    arr = np.fromfile(single, dtype=np.uint32, offset=HEADER_BYTES)
    weights = SHARD_WEIGHTS[: wl["num_shards"]]
    counts = [len(arr) * w // sum(weights) for w in weights]
    counts[-1] += len(arr) - sum(counts)
    shard_dir = os.path.join(common.BENCH_DIR, f"fileset_shards_{tag}")
    paths = [os.path.join(shard_dir, f"shard_{i:05d}.bin")
             for i in range(len(counts))]
    if not all(os.path.exists(p) for p in paths):
        paths = write_token_shards(shard_dir, arr, counts)
    return single, FileSet.build(paths), arr


def _pipe(source, wl: dict, **kw) -> CkIOPipeline:
    return CkIOPipeline(
        source, wl["global_batch"], wl["seq_len"], num_pes=NUM_PES,
        num_consumers=16,
        file_opts=FileOptions(num_readers=NUM_READERS,
                              splinter_bytes=wl["splinter_bytes"]),
        **kw,
    )


def drain_host(source, wl: dict):
    """Whole-window host drain; returns (median s/step, batches, metrics)."""
    pipe = _pipe(source, wl)
    copied = []
    pipe.ck.director.add_observer(lambda sm: copied.append(sm.bytes_copied))
    for w in range(WARM_STEPS):
        pipe.get_batch(w)
    steps_s, batches = [], []
    for s in range(WARM_STEPS, WARM_STEPS + wl["steps"]):
        t0 = time.perf_counter()
        x, y = pipe.get_batch(s)
        steps_s.append(time.perf_counter() - t0)
        batches.append((np.array(x), np.array(y)))   # copy out of the arena
    pipe.close()                 # sessions merge into ShardMetrics on close
    shards = pipe.ck.director.shards.summary()
    return statistics.median(steps_s), batches, copied, shards


def mesh_devices() -> list:
    """The devices Part B shards over: 8 host devices on the CPU, or every
    accelerator present. Raises with what was found when there are fewer."""
    import jax

    devs = jax.devices()
    want = HOST_DEVICES if devs[0].platform == "cpu" else 2
    if len(devs) < want:
        hint = (f"; start the process with XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={HOST_DEVICES}"
                if devs[0].platform == "cpu" else "")
        raise RuntimeError(
            f"perf_fileset needs {want} {devs[0].platform} devices, found "
            f"{len(devs)}{hint}")
    return devs[:HOST_DEVICES] if devs[0].platform == "cpu" else devs


def _mesh_sharding(devs, flat: bool = False):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = np.array(devs)
    # The constructor path shards the assembled (batch, seq+1) window; the
    # legacy per-call path forwards the sharding to a device_put of the
    # *flat* 1-D token window, so it needs the rank-1 spec.
    spec = PartitionSpec("dp") if flat else PartitionSpec("dp", None)
    return NamedSharding(Mesh(devs, ("dp",)), spec)


def run_sharded(fs: FileSet, wl: dict, devs, constructor: bool):
    """Streamed drain into a batch sharding over ``devs``.

    ``constructor=True`` ships the sharding at pipeline construction (this
    PR's path: per-chunk placement); ``False`` passes it per call (the
    legacy warn-and-restage fallback). Returns batches + both ledgers."""
    import jax

    sh = _mesh_sharding(devs, flat=not constructor)
    pipe = _pipe(fs, wl, streaming=True,
                 sharding=sh if constructor else None)
    rt_warnings = 0
    batches = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for s in range(WARM_STEPS + wl["steps"]):
            if constructor:
                x, y = pipe.get_batch_device(s)
            else:
                x, y = pipe.get_batch_device(s, sharding=sh)
            jax.block_until_ready((x, y))
            if s >= WARM_STEPS:
                batches.append((np.asarray(x), np.asarray(y)))
        rt_warnings = sum(
            1 for w in caught if issubclass(w.category, RuntimeWarning))
    pipe.close()                 # quiesce prefetch staging, merge sessions
    shards = pipe.ck.director.shards.summary()
    dev_bytes = dict(pipe.ck.director.shards.device_bytes)
    stream = pipe.stream.summary()
    ingest = pipe.ingest.summary()
    return batches, shards, dev_bytes, stream, ingest, rt_warnings


def _match(a, b) -> bool:
    return all(np.array_equal(x1, x2) and np.array_equal(y1, y2)
               for (x1, y1), (x2, y2) in zip(a, b))


def run(quick: bool = False) -> dict:
    devs = mesh_devices()
    ndev = len(devs)
    wl = workload(quick)
    single, fs, _ = build_corpus(wl)
    window_bytes = wl["global_batch"] * (wl["seq_len"] + 1) * 4

    # -- Part A: shard-transparent drain -----------------------------------
    drain_host(single, wl)                       # process warmup, discard
    single_s, ref_batches, single_copied, _ = drain_host(single, wl)
    fs_s, fs_batches, fs_copied, fs_shards = drain_host(fs, wl)
    drain_match = _match(ref_batches, fs_batches)
    total_read = (WARM_STEPS + wl["steps"]) * window_bytes

    # -- Part B: staged-bytes accounting on the device mesh ----------------
    ctor_b, ctor_sh, ctor_dev, ctor_strm, ctor_ing, ctor_warn = run_sharded(
        fs, wl, devs, constructor=True)
    leg_b, _, _, leg_strm, leg_ing, leg_warn = run_sharded(
        fs, wl, devs, constructor=False)
    measured = (WARM_STEPS + wl["steps"]) * window_bytes
    ctor_staged = int(ctor_sh["addressable_bytes"])
    # The stager also places the *prefetched* next window's chunks (the
    # overlap working as designed), so per-device put totals can exceed the
    # consumed share by whole windows — the invariant is perfect balance:
    # every device staged exactly total/ndev.
    total_puts = sum(ctor_dev.values())
    balanced = (len(ctor_dev) == ndev
                and max(ctor_dev.values()) == min(ctor_dev.values())
                and max(ctor_dev.values()) == total_puts // ndev)
    # Legacy fallback ledger: streamed chunks staged to the default device
    # while reads landed (then discarded), plus the whole-window restage
    # that satisfies the per-call sharding.
    leg_staged = int(leg_strm["bytes_staged"]) + int(leg_ing["h2d_bytes"])

    report = {
        "bench": "perf_fileset",
        "devices": ndev,
        "platform": devs[0].platform,
        "workload": {**wl, "window_bytes": window_bytes,
                     "num_readers": NUM_READERS,
                     "shard_weights": list(SHARD_WEIGHTS[:wl["num_shards"]])},
        "drain": {
            "single_s_per_step": round(single_s, 6),
            "fileset_s_per_step": round(fs_s, 6),
            "single_mbps": round(window_bytes / single_s / 1e6, 1),
            "fileset_mbps": round(window_bytes / fs_s / 1e6, 1),
            "fileset_over_single": round(fs_s / single_s, 3) if single_s
            else 0.0,
            "batches_match": bool(drain_match),
            "bytes_copied": int(sum(single_copied) + sum(fs_copied)),
            "shards_read": int(fs_shards["shards_read"]),
            "shard_read_bytes": int(fs_shards["shard_read_bytes"]),
            "shard_bytes_accounted": fs_shards["shard_read_bytes"]
            >= total_read,
        },
        "sharded_staging": {
            "window_bytes": window_bytes,
            "steps_measured": WARM_STEPS + wl["steps"],
            "ctor": {
                "staged_bytes": ctor_staged,
                "staged_per_step": ctor_staged // (WARM_STEPS + wl["steps"]),
                "window_bytes_total": int(ctor_sh["window_bytes"]),
                "staged_put_bytes": int(total_puts),
                "prefetched_bytes": int(total_puts - ctor_staged),
                "max_device_bytes": int(ctor_sh["max_device_bytes"]),
                "per_device_bytes": total_puts // ndev,
                "devices_staged": int(ctor_sh["devices_staged"]),
                "device_put_calls": int(ctor_sh["device_put_calls"]),
                "cross_host_placements": int(ctor_sh["cross_host_placements"]),
                "host_permute_bytes": int(ctor_ing["host_permute_bytes"]),
                "overlap_fraction": round(ctor_strm["overlap_fraction"], 4),
                "runtime_warnings": ctor_warn,
            },
            "legacy_per_call": {
                "staged_bytes": leg_staged,
                "staged_per_step": leg_staged // (WARM_STEPS + wl["steps"]),
                "streamed_then_discarded": int(leg_strm["bytes_staged"]),
                "whole_window_restage": int(leg_ing["h2d_bytes"]),
                "runtime_warnings": leg_warn,
            },
            "legacy_over_ctor": round(leg_staged / ctor_staged, 3)
            if ctor_staged else 0.0,
            "staged_equals_window": ctor_staged == measured
            and int(ctor_sh["window_bytes"]) == measured,
            "per_device_balanced": bool(balanced),
            "batches_match_reference": bool(
                _match(ctor_b, ref_batches) and _match(leg_b, ref_batches)),
        },
        "note": "Part A: one stream as a single file vs an uneven "
                "FileSet — bit-identical whole-window drains, zero "
                "bytes_copied, per-shard read accounting. Part B (every "
                "mesh device): constructor sharding stages exactly 1x window "
                "per step at window/ndev per device with no warning; the "
                "legacy per-call fallback warns and pays ~2x (streamed "
                "chunks discarded + whole-window restage).",
    }
    common.emit("fileset_drain_single", single_s * 1e6,
                f"{report['drain']['single_mbps']}MBps")
    common.emit("fileset_drain_sharded", fs_s * 1e6,
                f"{report['drain']['fileset_mbps']}MBps")
    common.emit("fileset_drain_ratio", 0.0,
                f"{report['drain']['fileset_over_single']:.3f}x")
    common.emit("fileset_staged_ratio", 0.0,
                f"{report['sharded_staging']['legacy_over_ctor']:.2f}x")
    common.write_report("fileset", report, quick)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small window / fewer steps (CI smoke)")
    args = ap.parse_args()
    try:
        report = run(quick=args.quick)
    except RuntimeError as e:
        raise SystemExit(str(e)) from None
    sh = report["sharded_staging"]
    ok = (report["drain"]["batches_match"]
          and report["drain"]["bytes_copied"] == 0
          and report["drain"]["shard_bytes_accounted"]
          and sh["staged_equals_window"]
          and sh["per_device_balanced"]
          and sh["ctor"]["cross_host_placements"] == 0
          and sh["ctor"]["host_permute_bytes"] == 0
          and sh["ctor"]["runtime_warnings"] == 0
          and sh["legacy_per_call"]["runtime_warnings"] >= 1
          and sh["legacy_over_ctor"] > 1.5
          and sh["batches_match_reference"])
    print(f"# drain ratio={report['drain']['fileset_over_single']}x "
          f"staged legacy/ctor={sh['legacy_over_ctor']}x "
          f"{'OK' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
