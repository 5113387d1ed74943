"""Data files of a cell at smoke size, for the CPU tests: a checkout-like
directory with its own BENCHMARK.json, configuration, traffic and limits,
read by the real harness, drivers and metric readers."""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)

SMOKE_SIZES = {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}
# The train cell's limits at smoke size, set like the cell's own from
# readings at this size (CPU, seeds 2**31 + 3, 2**31 + 5, 1 and 2): the
# program read at most loss 2.8e-5, first-gradient 1.6e-3 and change 1.1e-3;
# the float8 control at least 2.3e-4, 1.1e-2 and 4.0e-3; half the batch
# 2.5e-3, 4.7e-2 and 9.0e-3.
SMOKE_LIMITS = {"phi4mini-train4k.steady": {
    "batch_mismatch_tokens": 0, "loss_gap": 1e-4, "grad_norm_gap": 5e-3,
    "change_norm_gap": 2.5e-3}}
SMOKE_TRAIN = {"global_batch": 4, "seq_len": 64, "microbatches": 2,
               "min_step_s": 0.002, "trace_steps": 2}


def _read(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(root: str, cells, traffic_overrides=None, limits=None) -> str:
    """Writes ``root/BENCHMARK.json`` and the files it names for ``cells``,
    each ``(cell name, real cell it copies, chips)``: the real cell's
    configuration cut to smoke sizes, its traffic mix with the smoke job
    shape (and ``traffic_overrides``), and its limits (or ``limits``).
    Returns the BENCHMARK.json path."""
    real = _read("..", "BENCHMARK.json")
    by_name = {w["name"]: w for w in real["workloads"]}
    configs = {c["name"]: c for c in real["configs"]}
    bench = dict(real, configs=[], workloads=[])
    for name, copies, chips in cells:
        w = by_name[copies]
        conf = dict(configs[w["config"]], name=f"{name}-config",
                    file=f"perfbench/configs/{name}-config.json")
        c = dict(_read("..", configs[w["config"]]["file"]), **SMOKE_SIZES)
        _write(os.path.join(root, conf["file"]), c)
        tr = _read("traffic", f"{w['traffic']}.json")
        if tr["driver"] == "train":
            tr.update(SMOKE_TRAIN)
        tr.update(traffic_overrides or {})
        _write(os.path.join(root, "perfbench", "traffic",
                            f"{name}-mix.json"), tr)
        lim = (limits or SMOKE_LIMITS.get(copies)
               or _read("cells", f"{copies}.json")["limits"])
        _write(os.path.join(root, "perfbench", "cells", f"{name}.json"),
               {"limits": lim})
        bench["configs"].append(conf)
        bench["workloads"].append(dict(w, name=name, config=conf["name"],
                                       traffic=f"{name}-mix", chips=chips))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if copies in m.get("workloads", ()):
                m["workloads"] = list(m["workloads"]) + [name]
    path = os.path.join(root, "BENCHMARK.json")
    _write(path, bench)
    return path


def run(root: str, real_cell: str, *, trace: bool = False,
        seconds: float = 0.3, seed: int = 2**31 + 5, chips: int = 1,
        traffic_overrides=None, limits=None) -> dict:
    """One run of ``real_cell``'s smoke copy on the CPU, through the real
    harness; returns the result line's object."""
    import time

    from harness.main import run_cell

    name = f"smoke-{real_cell}"
    bench = make_root(root, [(name, real_cell, chips)],
                      traffic_overrides=traffic_overrides, limits=limits)
    return run_cell(name, seed, seconds, trace, started=time.perf_counter(),
                    platform="cpu", root=root, bench_file=bench)
