#!/usr/bin/env python3
"""Chip smoke test: the CkIO training path on a TPU, checked against NumPy.

    python chip_smoke.py             # one chip: the two training phases
    python chip_smoke.py --chips 4   # four chips: the sharded pipelines only

One chip runs ``repro.launch.train.run`` — ``CkIOPipeline`` ->
``get_batch_device`` -> on-device reassembly -> the jitted microbatched
train step under ``StepSupervisor`` — in two phases of a few steps each:

* ``device-ingest``: thread readers, one ``device_put`` per step window and
  the Pallas window kernel rebuilding the batch on the chip;
* ``streaming-service``: splinters staged as their reads land, read by the
  pooled worker processes of a ``ReaderService`` while this process holds
  the chip.

With ``--chips 4`` it runs instead the sharded streaming and whole-window
pipelines (``CkIOPipeline(sharding=...)``) over a 4-device ``data`` mesh,
feeding a data-parallel step with replicated parameters.

Every batch must equal, bit for bit, a NumPy read of the same window of the
token file, and every loss must be finite. Any failure exits non-zero. The
last line of standard output is the JSON result; it is printed only on a TPU.

Model and cut (phi4-mini-3.8b, arXiv:2412.08905). The published widths are
kept: d_model 3,072, 24 query and 8 KV heads of 128, d_ff 8,192. The
deployment is a ``train_4k`` job (global batch 256 x 4,096 tokens) on a
256-chip v5e pod: 32 data-parallel replicas of 8 pipeline stages, each stage
holding 4 of the 32 layers and an eighth of the 200,064-row vocabulary. One
chip holds one stage's share: 4 layers, 25,008 vocabulary rows and 8 of the
256 sequences, in 8 microbatches; weights are random from a fixed seed. The
four-chip run is 4 such replicas, global batch 32.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "phi4-mini-3.8b"
LAYERS = 4                  # one pipeline stage of 8 (32 layers published)
VOCAB = 200_064 // 8        # the stage's slice of the vocabulary
SEQ = 4096                  # train_4k
BATCH_PER_CHIP = 8          # 256 sequences over 32 data-parallel replicas
MICROBATCHES = 8
STEPS = 6
PHASES = (
    ("device-ingest", ("--device-ingest",)),
    ("streaming-service", ("--streaming", "--service")),
)


class SmokeFailure(Exception):
    """A check of the smoke run failed. Not a RuntimeError, so the train
    loop's supervisor does not retry past it."""


def cut_config():
    from repro.configs.registry import get_config

    return get_config(ARCH).replace(num_layers=LAYERS, vocab_size=VOCAB)


def write_corpus(path: str, *, steps: int, batch: int, seq: int,
                 vocab: int, seed: int) -> None:
    from repro.data import make_token_file

    make_token_file(path, steps * batch * (seq + 1) + 1024, vocab, seed=seed)


def reference_window(path: str, step: int, batch: int, seq: int):
    """NumPy read of step ``step``'s window: ``(inputs, labels)`` as stored."""
    import numpy as np

    from repro.data import read_meta

    meta = read_meta(path)
    n = batch * (seq + 1)
    rows = np.fromfile(path, dtype=meta.dtype, count=n,
                       offset=meta.data_offset + step * n * meta.itemsize)
    rows = rows.reshape(batch, seq + 1)
    return rows[:, :seq], rows[:, 1:]


def same_bits(got, want) -> bool:
    import numpy as np

    got = np.asarray(got)
    return (got.shape == want.shape and got.dtype.itemsize == want.itemsize
            and np.array_equal(got.view(want.dtype), want))


class BatchCheck:
    """``on_batch`` hook: each step's device batch against the file."""

    def __init__(self, path: str, batch: int, seq: int):
        self.path, self.batch, self.seq = path, batch, seq
        self.checked = 0

    def __call__(self, step: int, batch: dict) -> None:
        want_x, want_y = reference_window(self.path, step, self.batch,
                                          self.seq)
        if not (same_bits(batch["tokens"], want_x)
                and same_bits(batch["labels"], want_y)):
            raise SmokeFailure(f"step {step}: batch differs from the file")
        self.checked += 1


def run_phase(name: str, flags, cfg, workdir: str, *, batch: int, seq: int,
              microbatches: int, steps: int, seed: int) -> dict:
    """One training phase through ``repro.launch.train.run``; raises
    ``SmokeFailure`` unless every step ran once, matched the file and gave
    a finite loss."""
    from repro.launch import train

    path = os.path.join(workdir, f"{name}.tokens")
    write_corpus(path, steps=steps, batch=batch, seq=seq,
                 vocab=cfg.vocab_size, seed=seed)
    args = train.parse_args([
        "--steps", str(steps), "--global-batch", str(batch),
        "--seq", str(seq), "--microbatches", str(microbatches),
        "--data", path, "--ckpt-dir", os.path.join(workdir, f"{name}.ckpt"),
        *flags,
    ])
    check = BatchCheck(path, batch, seq)
    try:
        summary = train.run(cfg, args, on_batch=check)
    finally:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)   # GBs at full size
    losses = [e["loss"] for e in summary["log"]]
    if summary["steps"] != steps or summary["failures"]:
        raise SmokeFailure(f"{name}: {summary['steps']} steps ran, "
                           f"{summary['failures']} failed")
    if check.checked != steps:
        raise SmokeFailure(f"{name}: {check.checked} of {steps} batches "
                           "checked")
    if not all(math.isfinite(v) for v in losses):
        raise SmokeFailure(f"{name}: non-finite loss in {losses}")
    return {"phase": name, "steps": steps, "batches_identical": check.checked,
            "losses": losses,
            "step_wall_s": [e["wall_s"] for e in summary["log"]]}


def check_sharded(arr, want, devices) -> list:
    """``arr`` must be ``want`` with each device holding exactly its own
    contiguous block of rows. Returns the rows each device holds."""
    import numpy as np

    if not same_bits(arr, want):
        raise SmokeFailure("sharded batch differs from the file")
    per = want.shape[0] // len(devices)
    held = {}
    for shard in arr.addressable_shards:
        rows = shard.index[0]
        lo = rows.start or 0
        data = np.asarray(shard.data)
        if data.shape[0] != per or not same_bits(data, want[lo:lo + per]):
            raise SmokeFailure(f"{shard.device} holds rows {lo}.. of shape "
                               f"{data.shape}, not its {per} rows")
        held[shard.device] = held.get(shard.device, 0) + data.shape[0]
    if set(held) != set(devices):
        raise SmokeFailure(f"rows live on {sorted(map(str, held))}, not on "
                           f"each of {sorted(map(str, devices))}")
    return [held[d] for d in devices]


def run_sharded(cfg, workdir: str, devices, *, batch: int, seq: int,
                microbatches: int, steps: int, seed: int):
    """The sharded streaming and whole-window pipelines over a ``data`` mesh
    of ``devices``, feeding a data-parallel step (parameters replicated,
    batch rows sharded through ``in_shardings``). Yields one result per
    pipeline."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.data import CkIOPipeline
    from repro.models import build_model
    from repro.train import OptConfig, init_opt_state, make_train_step

    path = os.path.join(workdir, "sharded.tokens")
    write_corpus(path, steps=steps, batch=batch, seq=seq,
                 vocab=cfg.vocab_size, seed=seed)
    mesh = Mesh(np.array(devices), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    model = build_model(cfg)
    params = jax.jit(model.init, out_shardings=rep)(jax.random.PRNGKey(0))
    opt = jax.jit(init_opt_state, out_shardings=rep)(params)
    step_fn = jax.jit(
        make_train_step(model, OptConfig(warmup_steps=2, decay_steps=steps),
                        num_microbatches=microbatches),
        in_shardings=(rep, rep, rows), out_shardings=(rep, rep, None))
    for streaming in (True, False):
        name = "sharded-streaming" if streaming else "sharded-window"
        pipe = CkIOPipeline(path, batch, seq, sharding=rows,
                            streaming=streaming)
        losses, walls, held = [], [], None
        try:
            for s in range(steps):
                t0 = time.perf_counter()
                x, y = pipe.get_batch_device(s)
                want_x, want_y = reference_window(path, s, batch, seq)
                held = check_sharded(x, want_x, devices)
                check_sharded(y, want_y, devices)
                params, opt, m = step_fn(params, opt,
                                         {"tokens": x, "labels": y})
                losses.append(float(m["loss"]))
                walls.append(time.perf_counter() - t0)
        finally:
            pipe.close()
        if not all(math.isfinite(v) for v in losses):
            raise SmokeFailure(f"{name}: non-finite loss in {losses}")
        yield {"phase": name, "steps": steps, "batches_identical": steps,
               "rows_per_device": held, "losses": losses,
               "step_wall_s": walls}


def check_kernels(batch: int, seq: int, seed: int, *,
                  interpret: bool = False) -> dict:
    """The three reassembly kernels against NumPy on one step window: the
    window kernel (aligned, unaligned and remainder windows), the block
    gather and the token gather."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.reassemble import (
        reassemble_pallas,
        reassemble_tokens_pallas,
        reassemble_window_pallas,
    )

    rng = np.random.default_rng(seed)
    s1 = seq + 1
    n = batch * s1
    lin = rng.integers(1, 1 << 30, size=n + 7, dtype=np.int32)
    cases = 0

    def expect(got, want, what):
        nonlocal cases
        if not same_bits(got, want):
            raise SmokeFailure(f"{what} kernel differs from NumPy")
        cases += 1

    for off, valid in ((0, n), (7, n), (0, n - 100)):
        x, y = reassemble_window_pallas(
            jnp.asarray(lin), global_batch=batch, seq_len=seq,
            window_tok_off=off, valid_limit=off + valid, interpret=interpret)
        win = np.zeros(n, np.int32)             # pad_id 0 past valid_limit
        win[:valid] = lin[off:off + valid]
        rows = win.reshape(batch, s1)
        expect(x, rows[:, :seq], "window")
        expect(y, rows[:, 1:], "window")
    blocks = lin[:n].reshape(batch, s1)
    perm = rng.permutation(batch).astype(np.int32)
    expect(reassemble_pallas(jnp.asarray(blocks), jnp.asarray(perm),
                             interpret=interpret), blocks[perm], "block")
    order = rng.permutation(n)                  # an arbitrary staged layout
    row_idx = np.argsort(order).astype(np.int32).reshape(batch, s1)
    row_idx[-1, -50:] = -1
    x, y = reassemble_tokens_pallas(jnp.asarray(lin[:n][order]),
                                    jnp.asarray(row_idx), interpret=interpret)
    want = np.where(row_idx >= 0, blocks, 0)
    expect(x, want[:, :seq], "token")
    expect(y, want[:, 1:], "token")
    return {"phase": "kernels", "cases_identical": cases}


def ingest_executable_has_kernel(batch: int, seq: int) -> bool:
    """Whether the executable ``get_batch_device`` runs for a full window
    holds the Pallas kernel (a ``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    n = batch * (seq + 1)
    staged = jax.ShapeDtypeStruct((n,), jnp.int32)
    text = ops.reassemble_window.lower(
        staged, global_batch=batch, seq_len=seq, valid_limit=n,
    ).compile().as_text()
    return "tpu_custom_call" in text


def one_chip_phases(cfg, workdir: str, seed: int):
    if not ingest_executable_has_kernel(BATCH_PER_CHIP, SEQ):
        raise SmokeFailure("the ingest executable holds no Pallas kernel")
    yield {**check_kernels(BATCH_PER_CHIP, SEQ, seed),
           "tpu_custom_call_in_ingest": True}
    for name, flags in PHASES:
        yield run_phase(name, flags, cfg, workdir, batch=BATCH_PER_CHIP,
                        seq=SEQ, microbatches=MICROBATCHES, steps=STEPS,
                        seed=seed)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic token corpus")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices;"
              f" JAX found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    cfg = cut_config()
    print(f"model: {ARCH} cut to {cfg.num_layers} layers, vocab "
          f"{cfg.vocab_size}, {cfg.param_counts()['total'] / 1e6:.1f}M "
          f"params", flush=True)

    workdir = tempfile.mkdtemp(prefix=".chip_smoke-", dir=ROOT)
    try:
        if args.chips == 4:
            phases = run_sharded(
                cfg, workdir, devices[:4], batch=4 * BATCH_PER_CHIP,
                seq=SEQ, microbatches=MICROBATCHES, steps=STEPS,
                seed=args.seed)
        else:
            phases = one_chip_phases(cfg, workdir, args.seed)
        for result in phases:
            result["peak_bytes_in_use"] = peak_bytes(devices[0])
            print(json.dumps(result), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
