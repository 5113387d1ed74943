"""One run of one cell: find its pieces, check the devices, set up, measure,
compare with the reference, and build the result line."""
from __future__ import annotations

import os
import shutil
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from harness import checks as checks_mod
from harness.cells import BENCH_DIR, CHECKOUT, Cell, load_cell, metric_reader
from harness.spans import Spans


def work_dir(root: Optional[str] = None) -> str:
    """Where a run keeps its scratch files (corpus, checkpoint, trace): the
    benchmark directory's ``.work`` (gitignored), removed at the run's end."""
    if root is None:
        return os.path.join(BENCH_DIR, ".work")
    return os.path.join(root, os.path.basename(BENCH_DIR), ".work")


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


@dataclass
class Readings:
    """What the per-layer metric readers read."""
    spans: Spans
    counters: Dict[str, float]
    trace: object                 # harness.trace.Reduction, or None
    peaks: Dict[str, float]
    chips: int


@dataclass
class Outcome:
    """What a driver hands back."""
    end_to_end: Dict[str, float]
    checks: List[checks_mod.Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    window_compiles: int
    counters: Dict[str, float] = field(default_factory=dict)


@dataclass
class Ctx:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    workdir: str
    started: float                # host clock at process start
    spans: Spans = field(default_factory=Spans)

    def log(self, msg: str) -> None:
        log(msg)

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.workdir, "trace")

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.spans.tracing = True

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.spans.tracing = False


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, platform: str = "tpu",
             root: Optional[str] = None,
             bench_file: Optional[str] = None) -> dict:
    """Run ``workload`` once and return the result line's object (with the
    compared numbers under ``checks``, last). Raises ``NoChip`` when the
    devices do not match; ``platform`` other than "tpu" is for tests."""
    from harness import device

    cell = load_cell(workload, bench_file=bench_file, root=root)
    devices = device.require_chips(cell.chips, platform=platform)
    desc = device.describe(devices)
    peaks = device.peaks(desc["kind"]) if platform == "tpu" else {}
    log(f"cell {cell.name}: {desc['platform']} {desc['kind']} "
        f"x{desc['count']}, seed {seed}, {seconds} s, trace {int(trace)}")

    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    workdir = work_dir(root)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
              devices=devices, workdir=workdir, started=started)
    try:
        out = cell.driver().run(ctx)
        reduction = None
        if trace:
            from harness import trace as trace_mod

            reduction = trace_mod.reduce_dir(
                ctx.trace_dir, devices=len(devices),
                host_spans=tuple(ctx.spans.durations))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    log(f"compilations inside the measured window: {out.window_compiles}")
    result = {
        "correct": checks_mod.all_ok(out.checks),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": {},
        "device": dict(desc, memory_peak_bytes=out.memory_peak_bytes),
    }
    if trace:
        readings = Readings(spans=ctx.spans, counters=out.counters,
                            trace=reduction, peaks=peaks, chips=len(devices))
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(readings)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduction is not None:
            result["device"]["busy_s"] = reduction.busy_s
            result["device"]["window_s"] = reduction.window_s
            result["breakdown"] = reduction.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for name, value in out.end_to_end.items():
            if name in units:
                result["metrics"][name] = {"value": value,
                                           "unit": units[name]}
    result["checks"] = checks_mod.as_dict(out.checks)
    return result
