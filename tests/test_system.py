"""End-to-end behaviour tests: training through the CkIO pipeline converges,
restart resumes bit-exact, serving completes, dry-run lowers a real cell."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke_config
from repro.core import FileOptions
from repro.data import CkIOPipeline, make_token_file
from repro.models import build_model
from repro.train import (
    AsyncCheckpointer,
    OptConfig,
    StepSupervisor,
    init_opt_state,
    make_train_step,
    restore_tree,
)

KEY = jax.random.PRNGKey(0)


def test_train_e2e_through_ckio_pipeline(tmp_path):
    """The ChaNGa-analog: over-decomposed consumers feed a real train loop;
    loss must drop on a repeating corpus."""
    cfg = smoke_config(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg)
    path = str(tmp_path / "corpus.bin")
    steps, gb, seq = 12, 4, 32
    make_token_file(path, steps * gb * (seq + 1) + 64, cfg.vocab_size, seed=1)
    pipe = CkIOPipeline(path, gb, seq, num_pes=2, num_consumers=8,
                        file_opts=FileOptions(num_readers=2))
    params = model.init(KEY)
    opt = init_opt_state(params)
    step_jit = jax.jit(make_train_step(
        model, OptConfig(peak_lr=3e-3, warmup_steps=2, decay_steps=steps * 4),
        num_microbatches=2))
    losses = []
    for s in range(steps):
        x, y = pipe.get_batch(s % 4)   # cycle a small window -> memorizable
        params, opt, m = step_jit(params, opt,
                                  {"tokens": jnp.asarray(x),
                                   "labels": jnp.asarray(y)})
        losses.append(float(m["loss"]))
    pipe.close()
    assert losses[-1] < losses[0] - 0.1, losses


def test_restart_resumes_deterministically(tmp_path):
    """Kill-and-restart mid-run == uninterrupted run (checkpoint/replay)."""
    cfg = smoke_config(get_config("phi4-mini-3.8b")).replace(dtype="float32")
    model = build_model(cfg)
    opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=100)
    step_jit = jax.jit(make_train_step(model, opt_cfg))

    def batch_for(s):
        k = jax.random.PRNGKey(1000 + s)
        t = jax.random.randint(k, (2, 17), 0, cfg.vocab_size)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    def run(n_steps, state):
        for s in range(int(jax.device_get(state["opt"]["step"])), n_steps):
            p, o, _ = step_jit(state["params"], state["opt"], batch_for(s))
            state = {"params": p, "opt": o}
        return state

    params = model.init(KEY)
    ref_state = run(6, {"params": params, "opt": init_opt_state(params)})

    # interrupted run: 3 steps, checkpoint, "crash", restore, continue
    st = run(3, {"params": params, "opt": init_opt_state(params)})
    ck_path = str(tmp_path / "mid.ckpt")
    from repro.train import save_checkpoint

    save_checkpoint(ck_path, st, step=3)
    restored, step = restore_tree(ck_path, st)
    assert step == 3
    final = run(6, restored)

    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(final["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serving_batched_requests():
    cfg = smoke_config(get_config("recurrentgemma-2b"))
    model = build_model(cfg)
    params = model.init(KEY)
    from repro.serve import BatchServer, Request

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=8,
                                        dtype=np.int32),
                    max_new_tokens=4)
            for i in range(5)]
    out = BatchServer(model, params, batch_size=2).serve(reqs)
    assert all(r.result is not None and len(r.result) == 4 for r in out)


def test_greedy_generate_deterministic():
    cfg = smoke_config(get_config("codeqwen1.5-7b")).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    from repro.serve import greedy_generate

    prompt = jax.random.randint(KEY, (1, 6), 0, cfg.vocab_size)
    a = np.asarray(greedy_generate(model, params, prompt, 5))
    b = np.asarray(greedy_generate(model, params, prompt, 5))
    np.testing.assert_array_equal(a, b)


def test_dryrun_subprocess_lowers_real_cell(tmp_path):
    """The dry-run must boot with 512 placeholder devices and lower a real
    (arch × shape) cell in a fresh process."""
    out = str(tmp_path / "dry.jsonl")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "olmoe-1b-7b", "--shape", "decode_32k",
         "--mesh", "pod", "--no-compile", "--no-analyze", "--out", out],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    assert "error" not in rec, rec
    assert rec["chips"] == 256


def test_compile_cache_dir_honours_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert compile_cache.compile_cache_dir() == os.path.join(checkout,
                                                             ".jax_cache")


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo

    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag = (bf16[2,512]{1,0}, bf16[2,512]{1,0}) all-gather(bf16[1,512] %a, bf16[1,512] %b), dimensions={0}
  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %y), dimensions={0}
  %cp = u32[16]{0} collective-permute(u32[16]{0} %z), source_target_pairs={{0,1}}
  %nope = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %q)
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == 4096
    assert got["all-gather"] == 2 * 2 * 512 * 2
    assert got["reduce-scatter"] == 256
    assert got["collective-permute"] == 64
    assert got["count"] == 4
