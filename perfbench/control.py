#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set, on the chip.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--fault-seeds 1,2,3] [--out FILE]

For each seed it reads the numbers the cell compares, for the program as
the cell runs it (the lower readings), for the control (the reference put
in the program's place at the next precision down: float8 matmul operands
for a bfloat16 train step, bfloat16 leaves for a float32 checkpoint) and,
for training, for a step that leaves out half of its batch. All seeds run in
one process, so that the programs compile once. The benchmark's own runs do
not run this; its readings and the limits set from them are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.perf_counter()


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def train_readings(ctx, seeds, control_seeds, fault_seeds):
    import repro.train as program_train
    from harness import faults
    from harness.spans import Spans

    drv = ctx.cell.driver()
    tr = ctx.cell.traffic
    n = tr["checked_steps"]
    job = drv.Job(ctx.cell.config, tr, ctx.devices)
    half = None
    if fault_seeds:
        original = program_train.make_train_step
        program_train.make_train_step = faults.half_batch(original)
        try:
            half = drv.Job(ctx.cell.config, tr, ctx.devices)
        finally:
            program_train.make_train_step = original
    unlimited = {k: float("inf") for k in ctx.cell.limits}
    path = os.path.join(ctx.workdir, "corpus.tokens")
    rows = []

    def program_run(j, seed):
        pipe, service = j.pipeline(path)
        try:
            trainer = drv.Trainer(j, seed, pipe, Spans())
            prog = trainer.first_steps(n, seed)
            trainer.close()
        finally:
            pipe.close()
            if service is not None:
                service.shutdown()
        mism = sum(drv.batch_mismatches(path, trainer.batches, j.B, j.S))
        return prog, mism

    for seed in seeds:
        t = time.perf_counter()
        job.corpus(path, seed, n + tr["prefetch_depth"] + 2)
        prog, mism = program_run(job, seed)
        ref = drv.reference_readings(job, path, seed, n)
        row = {"seed": seed, "program": _values(drv.compare(
            prog, ref, unlimited, mism)), "reference_s": None}
        row["reference_s"] = time.perf_counter() - t
        if seed in control_seeds:
            ctl = drv.reference_readings(job, path, seed, n, "float8_e4m3fn")
            row["control"] = _values(drv.compare(ctl, ref, unlimited, 0))
        if half is not None and seed in fault_seeds:
            bad, mism = program_run(half, seed)
            row["half_batch"] = _values(drv.compare(bad, ref, unlimited,
                                                    mism))
        ctx.log(json.dumps(row))
        rows.append(row)
    return rows


def resume_readings(ctx, seeds, control_seeds):
    from harness import faults
    from repro.train import checkpoint

    drv = ctx.cell.driver()
    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        res = drv.Resumer(ctx.cell.config, ctx.cell.traffic, ctx.devices,
                          ctx.workdir)
        res.write(seed)
        row = {"seed": seed}
        kinds = [("program", None)] if seed in seeds else []
        if seed in control_seeds:
            kinds.append(("control", faults.rounded))
        for name, fault in kinds:
            original = checkpoint.restore_sharded
            if fault is not None:
                checkpoint.restore_sharded = fault(original)
            try:
                last = res.resume()
            finally:
                checkpoint.restore_sharded = original
            fps = [res.fingerprint(last)]
            compared, _ = res.compare(seed, fps, last, ctx.cell.limits)
            drv.free(last)
            row[name] = _values(compared)
        os.remove(res.path)
        ctx.log(json.dumps(row))
        rows.append(row)
    return rows


def _values(compared):
    return {c.name: c.value for c in compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu",
                    help="tpu, or cpu for the tests at smoke size")
    ap.add_argument("--root", default=None,
                    help="checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness import device
    from harness.cells import CHECKOUT, load_cell
    from harness.main import Ctx, log, work_dir

    cell = load_cell(args.workload, root=args.root)
    try:
        devices = device.require_chips(cell.chips, platform=args.platform)
    except device.NoChip as e:
        log(str(e))
        return 2
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    workdir = work_dir(args.root)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = Ctx(cell=cell, seed=0, seconds=0, trace=False, devices=devices,
              workdir=workdir, started=STARTED)
    try:
        if cell.traffic["driver"] == "train":
            rows = train_readings(ctx, args.seeds, args.control_seeds,
                                  args.fault_seeds)
        else:
            rows = resume_readings(ctx, args.seeds, args.control_seeds)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    out = {"workload": cell.name, "device": device.describe(devices),
           "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
