"""The Moonlight cell's driver at smoke size on the CPU: a sound run is
correct, each fault it must catch makes it incorrect, the traced run's
readings give its two new metrics; and the stream-service cell, whose mix
the train driver runs, is correct at smoke size."""
import json
import os
import sys
import time

import pytest

import smoke
from harness import faults_moe
from harness import main as harness_main
from harness.cells import metric_reader
from harness.spans import Spans
from harness.trace import Op, Reduction
from repro.core import tracing
from repro.core.tracing import Snapshot

CELL = "moonlight-train8k.steady"
SIZES = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "num_hidden_layers": 3, "vocab_size": 256,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "router_experts": 16, "first_expert": 4, "num_experts_per_tok": 4,
         "max_position_embeddings": 256}
TRAFFIC = {"global_batch": 2, "seq_len": 64, "microbatches": 2,
           "min_step_s": 0.002, "trace_steps": 2}
# Set like the cell's own limits from readings at this size (CPU, seeds
# 2**31 + 5 and 1): the program read at most loss 1.3e-3, first-gradient
# 1.4e-2, change 4.4e-3 and loads 1.8e-2; the float8 control at least
# 2.5e-3, 4.6e-2, 8.9e-3 and 5.9e-2; dropping above capacity 1.0 changed
# 9.5e-3 or more, leaving the bias out moved the loads 0.42 or more.
LIMITS = {"batch_mismatch_tokens": 0, "loss_gap": 2e-3, "grad_norm_gap": 3e-2,
          "change_norm_gap": 6.5e-3, "expert_load_gap": 3e-2}
NEW = ["expert_gmm_roofline", "expert_rows_max_over_mean.train"]


def run(root, trace=False, seed=2**31 + 5):
    from harness.main import run_cell

    name = f"smoke-{CELL}"
    bench = smoke.make_root(root, [(name, CELL, 1)],
                            traffic_overrides=TRAFFIC, limits=LIMITS)
    with open(os.path.join(smoke.BENCH_DIR, "configs",
                           "moonlight-train8k.json")) as f:
        config = dict(json.load(f), **SIZES)
    with open(os.path.join(root, "perfbench", "configs",
                           f"{name}-config.json"), "w") as f:
        json.dump(config, f)
    return run_cell(name, seed, 0.3, trace, started=time.perf_counter(),
                    platform="cpu", root=root, bench_file=bench)


def test_sound_run_is_correct(tmp_path, capsys):
    r = run(str(tmp_path))
    assert "compilations inside the measured window: 0" in \
        capsys.readouterr().err
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == set(LIMITS)
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault, name", [
    ("drop_above_capacity", "held_expert_rows"),
    ("bias_left_out", "sigmoid_route")])
def test_fault_is_incorrect(tmp_path, monkeypatch, fault, name):
    import repro.models.moe as program_moe

    wrap = getattr(faults_moe, fault)
    original = getattr(program_moe, name)
    monkeypatch.setattr(program_moe, name,
                        wrap(original, SIZES["router_experts"])
                        if fault == "drop_above_capacity" else wrap(original))
    r = run(str(tmp_path))
    assert not r["correct"], r["checks"]


def test_traced_run_counts_the_expert_rows(tmp_path, monkeypatch):
    seen, made = [], harness_main.Readings

    def keep(*a, **k):
        seen.append(made(*a, **k))
        return seen[-1]

    monkeypatch.setattr(harness_main, "Readings", keep)
    tracing.reset()
    r = run(str(tmp_path), trace=True)
    assert r["correct"], r["checks"]
    # No TPU in the trace: only the host span's metric is in the line.
    assert set(r["metrics"]) == {"input_wait_ms.train"}
    (got,) = seen
    counters = tracing.snapshot().counters
    tokens = TRAFFIC["global_batch"] * 64 * SIZES["num_experts_per_tok"]
    # 2 traced steps; the held experts take a share of every token's k rows
    assert 0 < counters["moe.routed_rows"] < 2 * 2 * tokens
    assert counters["moe.expert_row_groups"] == 2 * 2 * 2
    # a chip's trace, with the grouped matmul's operations named as the
    # TPU compiler names them
    got.trace = Reduction(window=(0, 10**9), devices=1, busy_ns=[10**9],
                          ops=[Op(0, "jit_train_step/ragged-dot-none",
                                  "jit_train_step", 0, 10**6, 10**6)])
    got.peaks = {"bf16_flops_per_s": 1e12}
    ratio = metric_reader(NEW[1]).read(got)
    assert 1.0 <= ratio <= SIZES["n_routed_experts"]
    roof = metric_reader(NEW[0]).read(got)
    want = (100 * counters["moe.routed_rows"] * 2 * 3 * 64 * 32 * 3
            / 1e12 / 1e-3)
    assert roof == pytest.approx(want)


def readings(ops=(), **counters):
    trace = Reduction(window=(0, 10**9), devices=1, busy_ns=[10**9],
                      ops=list(ops))
    return harness_main.Readings(spans=Spans(), counters=counters,
                                 trace=trace, peaks={"bf16_flops_per_s": 1e12},
                                 chips=1)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_the_programs_counters(monkeypatch, name):
    import repro.core

    counters = {"steps": 2, "experts_held": 8, "expert_flops_per_row": 10.0}
    gmm = [Op(0, "jit_train_step/ragged-dot-none", "jit_train_step", 0, 10,
              10)]
    reader = metric_reader(name)
    monkeypatch.setattr(tracing, "snapshot", lambda: Snapshot([], {}))
    assert reader.read(readings(gmm, **counters)) is None
    monkeypatch.setattr(tracing, "snapshot", lambda: Snapshot([], {
        "moe.routed_rows": 800, "moe.expert_rows_max": 200,
        "moe.expert_row_groups": 2}))
    assert reader.read(readings(gmm, **counters)) is not None
    # A program without the tracing module, as before it existed.
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert reader.read(readings(gmm, **counters)) is None


def test_roofline_reads_only_the_grouped_matmul():
    counters = {"steps": 2, "experts_held": 8, "expert_flops_per_row": 1e6}
    ops = [Op(0, "jit_train_step/ragged-dot-none", "jit_train_step", 0,
              10**6, 10**6),
           Op(0, "jit_train_step/ragged-dot-metadata", "jit_train_step",
              10**6, 2 * 10**6, 10**6),
           Op(0, "jit_train_step/fusion", "jit_train_step", 0, 10**9, 10**9)]
    snap = Snapshot([], {"moe.routed_rows": 1000})
    import unittest.mock as mock

    with mock.patch.object(tracing, "snapshot", lambda: snap):
        got = metric_reader(NEW[0]).read(readings(ops, **counters))
    # 1e9 FLOPs at 1e12 FLOP/s is 1 ms, over 2 ms of grouped matmul
    assert got == pytest.approx(50.0)


def test_stream_service_mix_is_correct(tmp_path):
    r = smoke.run(str(tmp_path), "phi4mini-train4k.stream-service",
                  limits=smoke.SMOKE_LIMITS["phi4mini-train4k.steady"])
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
