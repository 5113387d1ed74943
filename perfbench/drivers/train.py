"""Steady training through the program's own path.

Each step fetches its batch with ``CkIOPipeline.get_batch_device`` (the
CkIO read session, the staging to the device and the on-device batch
assembly), runs the jitted ``make_train_step`` step, frees the state the step
consumed and reads the loss back to the host, as ``launch/train.run`` does.

Set-up makes the corpus and the weights from the seed, builds the pipeline
and the step, and runs the first ``checked_steps`` steps through the same
calls: the first compiles, and these are the steps the plain reference
follows. The window then runs steps back to back until ``--seconds`` have
passed and the last step started has finished.

Traffic parameters (``traffic/<mix>.json``): ``global_batch``, ``seq_len``,
``microbatches``, ``sharding`` (null, or "data" for a batch-axis
data-parallel mesh over the cell's chips with the parameters replicated),
``streaming``, ``service`` (null, or the reader service's options),
``backend``, ``num_readers``, ``num_consumers``, ``num_pes``,
``max_workers``, ``prefetch_depth``, ``optimizer`` (the OptConfig),
``checked_steps``, ``trace_steps``, ``min_step_s`` (sizes the corpus so
that the window never wraps)."""
from __future__ import annotations

import math
import os
import time

from harness import checks, corpus, device, flops, program, reference, state
from harness.compiles import CompileCounter
from harness.main import Outcome


class Job:
    """The program's objects for one configuration and traffic mix: model,
    jitted step and shardings, built once and used for any number of seeds."""

    def __init__(self, config: dict, traffic: dict, devices):
        import jax
        import numpy as np
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)

        from repro import train as program_train
        from repro.models import build_model

        self.c, self.tr, self.devices = config, traffic, devices
        self.cfg = program.model_config(config)
        self.model = build_model(self.cfg)
        self.B, self.S = traffic["global_batch"], traffic["seq_len"]
        self.opt = traffic["optimizer"]
        if traffic["sharding"] == "data":
            mesh = Mesh(np.array(devices), ("data",))
            self.rows = NamedSharding(mesh, P("data", None))
            self.rep = NamedSharding(mesh, P())
        elif traffic["sharding"] is None:
            self.rows, self.rep = None, SingleDeviceSharding(devices[0])
        else:
            raise ValueError(f"unknown sharding {traffic['sharding']!r}")
        step_fn = program_train.make_train_step(
            self.model, program_train.OptConfig(**self.opt),
            num_microbatches=traffic["microbatches"])
        if self.rows is None:
            self.step = jax.jit(step_fn)
        else:
            self.step = jax.jit(step_fn,
                                in_shardings=(self.rep, self.rep, self.rows),
                                out_shardings=(self.rep, self.rep, None))
        self.init_opt = jax.jit(program_train.init_opt_state,
                                out_shardings=self.rep)
        self.leaves = state.param_leaves(config)
        self.make = state.maker(self.leaves, out_shardings=self.rep)
        self.norms = jax.jit(reference.leaf_norms)
        self.diff_norms = jax.jit(lambda a, b: reference.leaf_norms(
            jax.tree.map(lambda x, y: x - y, a, b)))

    def params(self, seed: int):
        return self.make(state.base_key(seed))

    def corpus(self, path: str, seed: int, steps: int) -> None:
        corpus.write_corpus(path, steps * self.B * (self.S + 1),
                            self.c["vocab_size"], seed)

    def pipeline(self, path: str):
        """The pipeline and, where the mix asks for one, its reader service
        (the caller shuts it down after closing the pipeline)."""
        from repro.core import CkIO, FileOptions
        from repro.data import CkIOPipeline

        tr = self.tr
        service = None
        if tr["service"]:
            from repro.ipc.service import ReaderService, ServiceOptions

            service = ReaderService(ServiceOptions(**tr["service"]))
        ckio = CkIO(num_pes=tr["num_pes"], pes_per_node=tr["num_pes"])
        pipe = CkIOPipeline(
            path, self.B, self.S, ckio=ckio,
            num_consumers=tr["num_consumers"],
            file_opts=FileOptions(num_readers=tr["num_readers"],
                                  backend=tr["backend"],
                                  max_workers=tr["max_workers"],
                                  prefault_arena=tr["backend"] == "process"),
            service=service, prefetch_depth=tr["prefetch_depth"],
            streaming=tr["streaming"], sharding=self.rows)
        return pipe, service


class Trainer:
    """One seed's state, fed from one pipeline, stepped through the job's
    step. Keeps every batch it consumed, for the check after the window."""

    def __init__(self, job: Job, seed: int, pipe, spans):
        self.job, self.pipe, self.spans = job, pipe, spans
        self.params = job.params(seed)
        program.check_tree(self.params, job.model.abstract_params())
        self.opt = job.init_opt(self.params)
        self.next_step = 0
        self.batches = []          # (step, inputs, labels) as consumed
        self.losses = []

    def step(self) -> float:
        s = self.next_step
        with self.spans.span("get_batch_device"):
            x, y = self.pipe.get_batch_device(s)
        self.batches.append((s, x, y))
        with self.spans.span("step_dispatch"):
            params, opt, metrics = self.job.step(
                self.params, self.opt, {"tokens": x, "labels": y})
        free((self.params, self.opt))       # the step consumed them
        self.params, self.opt = params, opt
        with self.spans.span("loss_readback"):
            loss = float(metrics["loss"])
        self.next_step += 1
        self.losses.append(loss)
        return loss

    def first_steps(self, n: int, seed: int) -> dict:
        """Runs the first ``n`` steps and reads what the reference is
        compared with: the losses, each leaf's norm of the first gradient as
        the optimizer took it (its first moment over 1 - b1), and of the
        parameters' change over the ``n`` steps."""
        out = {}
        for i in range(n):
            self.step()
            if i == 0:
                b1 = self.job.opt["b1"]
                out["grad"] = [float(v) / (1 - b1)
                               for v in self.job.norms(self.opt["mu"])]
        p0 = self.job.params(seed)
        out["change"] = [float(v) for v in self.job.diff_norms(self.params, p0)]
        free(p0)
        out["loss"] = list(self.losses[:n])
        return out

    def close(self) -> None:
        free((self.params, self.opt))


def free(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def batch_mismatches(path: str, batches, B: int, S: int):
    """Mismatched tokens of each consumed batch against a NumPy read of its
    window from the file."""
    out = []
    for s, x, y in batches:
        want_x, want_y = corpus.reference_window(path, s, B, S)
        out.append(corpus.mismatched_tokens(x, want_x)
                   + corpus.mismatched_tokens(y, want_y))
    return out


def compare(prog: dict, ref: dict, limits: dict, mismatched: int):
    """The numbers compared with the reference, each with its limit."""
    raw = ref["grad_raw"]
    median = sorted(raw)[len(raw) // 2]
    moved = [g >= 1e-3 * median for g in raw]   # leaves Adam moves by more than round-off
    return [
        checks.Check("batch_mismatch_tokens", float(mismatched),
                     limits["batch_mismatch_tokens"]),
        checks.Check("loss_gap", checks.loss_gap(prog["loss"], ref["loss"]),
                     limits["loss_gap"]),
        checks.Check("grad_norm_gap",
                     checks.norm_gap(prog["grad"], ref["grad"]),
                     limits["grad_norm_gap"]),
        checks.Check("change_norm_gap",
                     checks.norm_gap(prog["change"], ref["change"], keep=moved),
                     limits["change_norm_gap"]),
    ]


def reference_readings(job: Job, path: str, seed: int, steps: int,
                       operand_dtype=None) -> dict:
    ref = reference.Reference(job.c, job.opt, operand_dtype)
    batches = [corpus.reference_window(path, s, job.B, job.S)
               for s in range(steps)]
    return ref.run(lambda: job.params(seed), batches)


def log_readings(ctx, names, prog: dict, ref: dict) -> None:
    ctx.log(f"losses program {prog['loss']} reference {ref['loss']}")
    for key in ("grad", "change"):
        for n, a, b in zip(names, prog[key], ref[key]):
            ctx.log(f"{key} norm {n}: program {a!r} reference {b!r}")


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
    return Outcome(
        end_to_end=end_to_end,
        checks=compare(prog, ref, ctx.cell.limits, sum(mism)),
        attempted=len(window), failed=sum(bad),
        memory_peak_bytes=peak, window_compiles=compiles,
        counters={
            "steps": len(window),
            "flops_per_step": flops.train_step_flops(ctx.cell.config,
                                                     job.B, job.S),
            "assemble_bytes_per_step": flops.assemble_bytes(job.B, job.S),
        })
