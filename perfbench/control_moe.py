#!/usr/bin/env python3
"""Readings from which the Moonlight cell's correctness limits are set, on
the chip.

    python3 perfbench/control_moe.py --workload moonlight-train8k.steady \
        --seeds 1,2,3 --control-seeds 1,2 --fault-seeds 1,2 [--out FILE]

For each seed it reads the numbers the cell compares (``drivers/train_moe``)
for the program as the cell runs it (the lower readings); for the control,
the reference put in the program's place with float8 matmul operands; and,
for the fault seeds, for the program with each fault of
``harness/faults_moe.py`` planted: dispatch that drops tokens above
capacity 1.0, and routing that leaves the correction bias out. One
reference run per seed serves them all, and all seeds run in one process.
The benchmark's own runs do not run this; its readings and the limits set
from them are in ``cells/<cell>.json`` and PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.perf_counter()


def readings(ctx, seeds, control_seeds, fault_seeds):
    import repro.models.moe as program_moe
    from control import _values
    from harness import corpus, faults_moe
    from harness.spans import Spans

    drv = ctx.cell.driver()
    tr = ctx.cell.traffic
    n = tr["checked_steps"]
    experts = ctx.cell.config["router_experts"]
    faults = {
        "drop_above_capacity": ("held_expert_rows", lambda f: (
            faults_moe.drop_above_capacity(f, experts))),
        "bias_left_out": ("sigmoid_route", faults_moe.bias_left_out),
    }
    unlimited = dict.fromkeys(drv.CHECKS, float("inf"))
    path = os.path.join(ctx.workdir, "corpus.tokens")
    jobs = {}

    def program_run(seed, fault=None):
        if fault is not None:      # planted while the step is traced
            name, wrap = faults[fault]
            original = getattr(program_moe, name)
            setattr(program_moe, name, wrap(original))
        try:
            if fault not in jobs:
                jobs[fault] = drv.Job(ctx.cell.config, tr, ctx.devices)
            job = jobs[fault]
            pipe, service = job.pipeline(path)
            try:
                trainer = drv.Trainer(job, seed, pipe, Spans())
                prog = trainer.first_steps(n, seed)
                trainer.close()
            finally:
                pipe.close()
                if service is not None:
                    service.shutdown()
        finally:
            if fault is not None:
                setattr(program_moe, name, original)
        mism = sum(drv.batch_mismatches(path, trainer.batches, job.B, job.S))
        return job, prog, mism

    rows = []
    for seed in seeds:
        t = time.perf_counter()
        corpus.write_corpus(
            path, (n + tr["prefetch_depth"] + 2) * tr["global_batch"]
            * (tr["seq_len"] + 1), ctx.cell.config["vocab_size"], seed)
        job, prog, mism = program_run(seed)
        ref = drv.reference_readings(job, path, seed, n)
        row = {"seed": seed,
               "program": _values(drv.compare(prog, ref, unlimited, mism))}
        if seed in control_seeds:
            ctl = drv.reference_readings(job, path, seed, n, "float8_e4m3fn")
            row["control"] = _values(drv.compare(ctl, ref, unlimited, 0))
        if seed in fault_seeds:
            for fault in faults:
                _, bad, mism = program_run(seed, fault)
                row[fault] = _values(drv.compare(bad, ref, unlimited, mism))
        row["seconds"] = time.perf_counter() - t
        ctx.log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu",
                    help="tpu, or cpu for the tests at smoke size")
    ap.add_argument("--root", default=None,
                    help="checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from control import _seeds
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
        rows = readings(ctx, _seeds(args.seeds), _seeds(args.control_seeds),
                        _seeds(args.fault_seeds))
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
