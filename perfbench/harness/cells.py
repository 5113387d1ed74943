"""Finding a cell's pieces by name: its configuration file, its traffic
mix, its limits, its driver and its per-layer metric readers. Adding a cell,
a mix, a driver or a metric adds files; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration file's contents
    traffic: dict                # the traffic mix's parameters
    limits: dict                 # the cell's correctness limits
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)

    def driver(self):
        name = self.traffic["driver"]
        return load_module(os.path.join(BENCH_DIR, "drivers", f"{name}.py"),
                           f"perfbench_driver_{name}")


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, whose
    ``read(readings)`` returns the value or None."""
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                       f"perfbench_metric_{name.replace('.', '_')}")


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(workload: str, bench_file: Optional[str] = None,
              root: Optional[str] = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``. Files it names are
    relative to ``root`` (the checkout)."""
    root = root or CHECKOUT
    bench = _load_json(bench_file or os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    bench_dir = os.path.join(root, bench["paths"][0])
    return Cell(
        name=workload, chips=w["chips"],
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        f"{w['traffic']}.json")),
        limits=_load_json(os.path.join(bench_dir, "cells",
                                       f"{workload}.json"))["limits"],
        end_to_end=e2e, per_layer=layer)
