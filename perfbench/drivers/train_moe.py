"""Steady training of a DeepSeek-V3-style configuration (Moonlight) through
the program's own path: the train driver's loop, with the model, its state,
its reference and its FLOPs taken from the MoE modules of the harness.

Each step fetches its batch with ``CkIOPipeline.get_batch_device``, runs the
jitted ``make_train_step`` step (state donated: a chip holds the state once,
as a training job does), reads the loss back and, while a trace is taken,
the step's expert-row counters (``repro.train.count_step``). Set-up and the
window are as in ``drivers/train.py``; so are the checks, with one more:
``expert_load_gap``, the first step's per-expert loads against the
reference's, sum |program - reference| over the sum of the loads. A run
makes the checks its cell's limits name (``CHECKS`` are all it can make).

Traffic parameters as ``drivers/train.py`` takes them."""
from __future__ import annotations

import math
import os
import time

from harness import checks, device, flops, flops_moe, reference, state_moe
from harness.cells import BENCH_DIR, load_module
from harness.compiles import CompileCounter
from harness.main import Outcome
from harness.reference_mla_moe import Reference

train = load_module(os.path.join(BENCH_DIR, "drivers", "train.py"),
                    "perfbench_driver_train")
batch_mismatches, free, log_readings = (train.batch_mismatches, train.free,
                                        train.log_readings)


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the registry's
    architecture ``c["arch"]`` with every size the file states."""
    from repro.configs.registry import get_config

    cfg = get_config(c["arch"]).replace(
        num_layers=c["num_hidden_layers"],
        first_dense_layers=c["first_k_dense_replace"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        vocab_size=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
        max_position=c["max_position_embeddings"],
        num_experts=c["router_experts"], experts_held=c["n_routed_experts"],
        first_expert=c["first_expert"], top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"],
        routed_scaling=c["routed_scaling_factor"],
        bias_update_rate=c["bias_update_speed"],
        router_aux_coef=c["seq_aux_alpha"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"])
    stated = (c["hidden_act"], c["compute_dtype"], c["param_dtype"],
              c["scoring_func"], c["topk_method"], c["n_group"],
              c["q_lora_rank"], c["norm_topk_prob"])
    runs = (cfg.act, cfg.dtype, cfg.param_dtype, cfg.router_score,
            "noaux_tc", 1, None, True)
    if stated != runs:
        raise ValueError(f"{c['arch']}: the program runs {runs}, the "
                         f"configuration file states {stated}")
    return cfg


class Job(train.Job):
    """The train driver's job for this configuration: the program's model
    and jitted step, the benchmark's state maker and norms."""

    def __init__(self, config: dict, traffic: dict, devices):
        import jax
        from jax.sharding import SingleDeviceSharding

        from repro import train as program_train
        from repro.models import build_model

        if traffic["sharding"] is not None:
            raise ValueError("this driver runs on one chip (sharding null)")
        self.c, self.tr, self.devices = config, traffic, devices
        self.cfg = model_config(config)
        self.model = build_model(self.cfg)
        self.B, self.S = traffic["global_batch"], traffic["seq_len"]
        self.opt = traffic["optimizer"]
        self.rows, self.rep = None, SingleDeviceSharding(devices[0])
        step = jax.jit(program_train.make_train_step(
            self.model, program_train.OptConfig(**self.opt),
            num_microbatches=traffic["microbatches"]), donate_argnums=(0, 1))
        self.last_metrics = None

        def step_and_keep(params, opt, batch):
            params, opt, self.last_metrics = step(params, opt, batch)
            return params, opt, self.last_metrics

        self.step = step_and_keep
        self.init_opt = jax.jit(program_train.init_opt_state,
                                out_shardings=self.rep)
        self.leaves = state_moe.param_leaves(config)
        self.make = state_moe.maker(self.leaves, config,
                                    out_shardings=self.rep)
        self.norms = jax.jit(reference.leaf_norms)
        self.diff_norms = jax.jit(lambda a, b: reference.leaf_norms(
            jax.tree.map(lambda x, y: x - y, a, b)))


class Trainer(train.Trainer):
    """The train driver's trainer; after each step it hands the step's
    metrics to the program's counters, and it keeps the first step's
    per-expert loads."""

    def step(self) -> float:
        import jax
        import numpy as np

        from repro.train import count_step

        loss = super().step()
        metrics = self.job.last_metrics
        count_step(metrics)
        if self.next_step == 1:
            self.first_load = [float(v) for leaf in jax.tree.leaves(
                metrics["moe"]["load"]) for v in np.ravel(leaf)]
        return loss

    def first_steps(self, n: int, seed: int) -> dict:
        out = super().first_steps(n, seed)
        out["load"] = self.first_load
        return out


def load_gap(got, want) -> float:
    """sum |got - want| over sum want, over every (layer, expert) load."""
    total = sum(want)
    if total <= 0 or not all(math.isfinite(g) for g in got):
        return math.inf
    return sum(abs(g - w) for g, w in zip(got, want)) / total


CHECKS = ("batch_mismatch_tokens", "loss_gap", "grad_norm_gap",
          "change_norm_gap", "expert_load_gap")


def compare(prog: dict, ref: dict, limits: dict, mismatched: int):
    """The numbers compared with the reference that ``limits`` names, each
    with its limit."""
    made = train.compare(prog, ref, dict.fromkeys(CHECKS, math.inf),
                         mismatched) + [
        checks.Check("expert_load_gap", load_gap(prog["load"], ref["load"]),
                     math.inf)]
    return [checks.Check(c.name, c.value, limits[c.name]) for c in made
            if c.name in limits]


def reference_readings(job: Job, path: str, seed: int, steps: int,
                       operand_dtype=None) -> dict:
    from harness import corpus

    ref = Reference(job.c, job.opt, operand_dtype)
    batches = [corpus.reference_window(path, s, job.B, job.S)
               for s in range(steps)]
    return ref.run(lambda: job.params(seed), batches)


def run(ctx) -> Outcome:
    tr = ctx.cell.traffic
    job = Job(ctx.cell.config, tr, ctx.devices)
    n_checked = tr["checked_steps"]
    steps = (n_checked + tr["trace_steps"] + tr["prefetch_depth"] + 2
             + math.ceil(ctx.seconds / tr["min_step_s"]))
    path = os.path.join(ctx.workdir, "corpus.tokens")
    job.corpus(path, ctx.seed, steps)
    pipe, service = job.pipeline(path)
    counter = CompileCounter()
    try:
        trainer = Trainer(job, ctx.seed, pipe, ctx.spans)
        prog = trainer.first_steps(n_checked, ctx.seed)
        ctx.spans.clear()
        window_start = len(trainer.batches)
        if ctx.trace:
            trainer.step()                   # one steady step before the trace
            window_start += 1
            ctx.spans.clear()
            counter.start()
            ctx.start_trace()
            for _ in range(tr["trace_steps"]):
                trainer.step()
            ctx.stop_trace()
            compiles = counter.stop()
            end_to_end = {}
        else:
            counter.start()
            t0 = time.perf_counter()
            setup_s = t0 - ctx.started
            while True:
                if trainer.next_step >= pipe.num_steps:
                    raise RuntimeError("the corpus ran out inside the window")
                trainer.step()
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            elapsed = time.perf_counter() - t0
            compiles = counter.stop()
            n = len(trainer.batches) - window_start
            end_to_end = {"train_tokens_per_s": n * job.B * job.S / elapsed,
                          "setup_s": setup_s}
            ctx.log(f"window: {n} steps in {elapsed!r} s")
        peak = device.memory_peak_bytes(ctx.devices)
        trainer.close()
    finally:
        pipe.close()
        if service is not None:
            service.shutdown()

    window = trainer.batches[window_start:]
    losses = trainer.losses[window_start:]
    mism = batch_mismatches(path, trainer.batches, job.B, job.S)
    bad = [m > 0 or not math.isfinite(l)
           for m, l in zip(mism[window_start:], losses)]
    trainer.batches.clear()
    t = time.perf_counter()
    ref = reference_readings(job, path, ctx.seed, n_checked)
    ctx.log(f"reference: {n_checked} steps in {time.perf_counter() - t!r} s")
    log_readings(ctx, reference.leaf_names(job.model.abstract_params()),
                 prog, ref)
    ctx.log(f"first-step loads: program {prog['load']} reference {ref['load']}")
    return Outcome(
        end_to_end=end_to_end,
        checks=compare(prog, ref, ctx.cell.limits, sum(mism)),
        attempted=len(window), failed=sum(bad),
        memory_peak_bytes=peak, window_compiles=compiles,
        counters={
            "steps": len(window),
            "flops_per_step": flops_moe.train_step_flops(ctx.cell.config,
                                                         job.B, job.S),
            "assemble_bytes_per_step": flops.assemble_bytes(job.B, job.S),
            "expert_flops_per_row": flops_moe.expert_flops_per_row(
                ctx.cell.config),
            "experts_held": ctx.cell.config["n_routed_experts"],
        })
