"""End-to-end training driver: CkIO input pipeline + supervised train loop.

This is the "ChaNGa integration" path run for real (CPU-sized): synthetic
corpus -> CkIO read sessions -> double-buffered batches -> jitted microbatched
train step -> async checkpoints -> fault-tolerant supervisor. On a pod, the
same driver runs with the production mesh (per-host pipelines feeding
device_put with NamedSharding).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
      --smoke --steps 50 --global-batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, smoke_config
from repro.core import CkIO, FileOptions, Topology
from repro.data import CkIOPipeline, make_token_file
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import (
    AsyncCheckpointer,
    OptConfig,
    StepSupervisor,
    init_opt_state,
    make_train_step,
)


def parse_args(argv=None) -> argparse.Namespace:
    """The driver's options, normalized (``--service`` implies the process
    backend, ``--streaming`` implies ``--device-ingest``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--num-readers", type=int, default=4)
    ap.add_argument("--num-consumers", type=int, default=16)
    ap.add_argument("--data", nargs="+",
                    default=["/tmp/repro_train_tokens.bin"],
                    help="token file path(s); more than one path opens the"
                         " list as a FileSet — one logical global row space"
                         " over all shards (data/fileset.py), read through"
                         " one shard-aware session per step window")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpts")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compression", default=None, choices=[None, "bf16"])
    ap.add_argument("--device-ingest", action="store_true",
                    help="one device_put of the whole step window + on-device"
                         " batch reassembly (kernels/reassemble.py) instead"
                         " of host-side batch construction")
    ap.add_argument("--streaming", action="store_true",
                    help="event-driven splinter streaming: stage each"
                         " splinter host->device as its read completes and"
                         " reassemble from arrival order on device (implies"
                         " --device-ingest; StreamMetrics in the final"
                         " summary prove the read/staging overlap)")
    ap.add_argument("--topology", default=None,
                    help="NUMA topology for the reader runtime: 'auto'"
                         " detects the host's NUMA nodes from sysfs (with"
                         " CPU sets for --numa-pin); an integer subdivides"
                         " each logical node into that many memory domains."
                         " Enables domain-coalesced pieces, cross-domain"
                         " delivery accounting, and first-touch arena"
                         " striping (each reader thread faults its own"
                         " stripe's pages on its own domain)")
    ap.add_argument("--numa-pin", action="store_true",
                    help="pin each reader I/O thread to the host CPUs of"
                         " its stripe's NUMA domain (requires --topology"
                         " auto for the CPU map; best-effort — outcomes"
                         " are counted in the locality summary)")
    ap.add_argument("--placement", default="node_spread",
                    choices=["round_robin", "node_spread", "domain_spread",
                             "near_consumers"],
                    help="reader->PE placement policy (core/placement.py);"
                         " near_consumers/domain_spread use --topology"
                         " when given")
    ap.add_argument("--backend", default="thread",
                    choices=["thread", "process"],
                    help="reader backend: 'thread' (helper I/O threads in"
                         " this process) or 'process' (real reader worker"
                         " processes preadv-ing into a shared-memory arena,"
                         " splinter events over cross-process rings —"
                         " src/repro/ipc). Zero-copy delivery and streaming"
                         " work identically; with --numa-pin the workers"
                         " sched_setaffinity-pin themselves, so pinning"
                         " spans real CPU sets")
    ap.add_argument("--max-workers", type=int, default=4,
                    help="process backend: cap on reader worker processes"
                         " per session")
    ap.add_argument("--service", action="store_true",
                    help="process backend: run every step session on a"
                         " persistent reader service (ipc/service.py) —"
                         " pooled long-lived workers re-armed per session"
                         " through shm mailboxes and recycled prefaulted"
                         " arenas, instead of spawning processes and"
                         " creating a fresh segment each step. Implies"
                         " --backend process")
    ap.add_argument("--pool-workers", type=int, default=4,
                    help="--service: persistent workers in the pool"
                         " (sessions check workers out per step; sizing it"
                         " at --max-workers keeps a step fully parallel)")
    ap.add_argument("--adaptive-splinters", action="store_true",
                    help="size splinters per session from observed"
                         " per-reader throughput + steal pressure"
                         " (core/autotune.py SplinterSizer); with"
                         " --streaming each size change retraces the fused"
                         " ingest once until the EMA converges")
    ap.add_argument("--tuned-env", action="store_true",
                    help="re-exec this driver through scripts/env.sh first"
                         " (tcmalloc LD_PRELOAD when the host ships it,"
                         " quiet TF/XLA logging, single intra-op XLA"
                         " thread); every knob degrades silently, so this"
                         " is safe on any host")
    ap.add_argument("--direct-io", action="store_true",
                    help="open the corpus O_DIRECT: reads bypass the page"
                         " cache and DMA into the session arena (cold-cache"
                         " read engine, io/submit.py). Misaligned windows"
                         " fail fast with a DirectIOError — never a silent"
                         " buffered fallback")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="in-flight splinter reads per reader: 0/1 = the"
                         " blocking loop, >= 2 = depth-managed async"
                         " submission (io_uring when available, else a"
                         " preadv pool)")
    ap.add_argument("--readahead-mb", type=int, default=0,
                    help="WILLNEED window (MB) advised ahead of the async"
                         " submission frontier (buffered files only)")
    ap.add_argument("--submit-mode", default="auto",
                    choices=["auto", "io_uring", "threads"],
                    help="async submission backend selection")
    ap.add_argument("--adaptive-queue", action="store_true",
                    help="let the Director's QueueTuner pick (queue-depth,"
                         " readahead) per session from observed throughput;"
                         " the explicit flags then only seed the first"
                         " session")
    args = ap.parse_args(argv)
    if args.numa_pin and not args.topology:
        ap.error("--numa-pin requires --topology (the topology supplies "
                 "the domain->CPU map; without it nothing would be pinned)")
    if args.service:
        args.backend = "process"
    if args.streaming:
        args.device_ingest = True
    return args


def _reexec_tuned_env() -> None:
    # Re-exec through the env script so LD_PRELOAD (allocator) and
    # XLA_FLAGS exist before the interpreter and jax start. env.sh exports
    # CKIO_TUNED_ENV=1, which breaks the exec loop.
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    env_sh = os.path.join(root, "scripts", "env.sh")
    if os.path.exists(env_sh):
        argv = [sys.executable, "-m", "repro.launch.train", *sys.argv[1:]]
        refs = " ".join(
            ['"$0"'] + [f'"${{{i}}}"' for i in range(1, len(argv))])
        os.execvp("bash", [
            "bash", "-c", f'source "{env_sh}" && exec {refs}', *argv])
    print(f"--tuned-env: {env_sh} not found; continuing untuned",
          file=sys.stderr)


def main() -> None:
    args = parse_args()
    if args.tuned_env and not os.environ.get("CKIO_TUNED_ENV"):
        _reexec_tuned_env()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    print(json.dumps(run(cfg, args), indent=2))


def run(
    cfg,
    args: argparse.Namespace,
    *,
    on_batch: Optional[Callable[[int, dict], None]] = None,
) -> dict:
    """Train ``cfg`` for ``args.steps`` steps through the CkIO pipeline and
    return the run summary. ``on_batch(step, batch)`` sees each step's
    device batch before the step consumes it."""
    model = build_model(cfg)
    print(f"arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"params≈{cfg.param_counts()['total']/1e6:.1f}M")

    # -- corpus + CkIO pipeline ------------------------------------------------
    need = args.steps * args.global_batch * (args.seq + 1) + 1024
    per_shard = (need + len(args.data) - 1) // len(args.data)
    for i, p in enumerate(args.data):
        if not os.path.exists(p):
            print(f"writing synthetic corpus shard: {p} ({per_shard} tokens)")
            make_token_file(p, per_shard, cfg.vocab_size, seed=i)
    if len(args.data) > 1:
        # Multi-shard corpus: one FileSet manifest = one logical row space;
        # the pipeline below is unchanged (shard starts become hard stripe
        # bounds inside each session plan).
        from repro.data import FileSet

        data_source = FileSet.build(args.data)
        print(f"fileset: {data_source.describe()}")
    else:
        data_source = args.data[0]
    # One host: a single scheduler node of num_pes PEs, so the NUMA
    # topology's node grid matches the scheduler's (a mismatched grid is
    # rejected by place_readers at session start).
    num_pes = 4
    ckio = CkIO(num_pes=num_pes, pes_per_node=num_pes)
    topology = (Topology.from_spec(args.topology, num_pes=num_pes,
                                   pes_per_node=num_pes)
                if args.topology else None)
    with contextlib.ExitStack() as stack:
        service = None
        if args.service:
            from repro.ipc.service import ReaderService, ServiceOptions

            service = ReaderService(ServiceOptions(
                pool_workers=args.pool_workers))
            stack.callback(service.shutdown)
            print(f"reader service: pool of {args.pool_workers} persistent "
                  f"workers (steady-state sessions re-arm, not respawn)")
        pipe = CkIOPipeline(
            data_source, args.global_batch, args.seq,
            ckio=ckio, num_consumers=args.num_consumers,
            file_opts=FileOptions(num_readers=args.num_readers,
                                  adaptive_splinters=args.adaptive_splinters,
                                  placement=args.placement,
                                  topology=topology,
                                  numa_pin=args.numa_pin,
                                  prefault_arena=(topology is not None
                                                  or args.backend == "process"),
                                  backend=args.backend,
                                  max_workers=args.max_workers,
                                  direct_io=args.direct_io,
                                  queue_depth=args.queue_depth,
                                  readahead_bytes=args.readahead_mb * (1 << 20),
                                  submit_mode=args.submit_mode,
                                  adaptive_queue=args.adaptive_queue),
            service=service,
            streaming=args.streaming,
        )
        stack.callback(pipe.close)

        # -- state -------------------------------------------------------------
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": init_opt_state(params)}
        opt_cfg = OptConfig(peak_lr=args.lr,
                            warmup_steps=max(2, args.steps // 10),
                            decay_steps=args.steps)
        step_jit = jax.jit(make_train_step(
            model, opt_cfg, num_microbatches=args.microbatches,
            compression=args.compression,
        ))

        def step_fn(state, batch):
            p, o, metrics = step_jit(state["params"], state["opt"], batch)
            # The step consumes its input state: free it once the step is
            # done with it, so that no reference left to the first state
            # keeps a second copy of parameters and moments on the device.
            for leaf in jax.tree.leaves(state):
                if isinstance(leaf, jax.Array):
                    leaf.delete()
            return {"params": p, "opt": o}, metrics

        def batch_for(step: int):
            if args.device_ingest:
                # Device path: one host→device transfer of the whole window,
                # batch-major reassembly + label shift on device.
                x, y = pipe.get_batch_device(step % pipe.num_steps)
                batch = {"tokens": x, "labels": y}
            else:
                x, y = pipe.get_batch(step % pipe.num_steps)
                batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
            if on_batch is not None:
                on_batch(step, batch)
            return batch

        ck = AsyncCheckpointer(args.ckpt_dir, keep=3)
        stack.callback(ck.shutdown)
        sup = StepSupervisor(step_fn, ck, ckpt_every=args.ckpt_every)

        start = 0
        if args.resume and ck.latest():
            from repro.train import restore_tree

            state, start = restore_tree(ck.latest(), state)
            print(f"resumed from step {start}")

        log = []
        t0 = time.time()
        t_last = time.perf_counter()

        def on_metrics(step, m):
            nonlocal t_last
            loss = float(m["loss"])     # waits for the step's device work
            now = time.perf_counter()
            log.append({"step": step, "loss": loss, "wall_s": now - t_last})
            t_last = now
            if step % 10 == 0 or step == args.steps:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({(time.time()-t0)/max(step-start,1):.2f}s/step)")

        sup.run(state, batch_for, args.steps, start_step=start,
                on_metrics=on_metrics)
    summary = pipe.ck  # ckio instance
    return {
        "final_loss": log[-1]["loss"] if log else None,
        "first_loss": log[0]["loss"] if log else None,
        "steps": sup.stats.steps_run,
        "failures": sup.stats.failures,
        "log": log,
        "sched_tasks": summary.sched.stats,
        "ingest": pipe.ingest.summary(),
        "stream": pipe.stream.summary() if args.streaming else None,
        "locality": (summary.director.locality.summary()
                     if topology is not None else None),
        "shards": (summary.director.shards.summary()
                   if len(args.data) > 1 else None),
        "service": (service.metrics.summary() if service is not None
                    else None),
    }


if __name__ == "__main__":
    main()
