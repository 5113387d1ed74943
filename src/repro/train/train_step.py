"""Jittable train step: microbatched gradient accumulation + AdamW.

Microbatching bounds activation memory: the global batch is split into
``num_microbatches`` slices scanned sequentially, accumulating grads in
``accum_dtype`` (fp32 default; bf16 halves the accumulator footprint — a
§Perf lever for the 27B model). Remat policy lives inside the model's
scan-over-blocks. Gradient compression (bf16/int8+EF) optionally wraps the
accumulated grads before the optimizer.

A model with sigmoid-routed MoE layers (DeepSeek-V3) also returns its
experts' loads (``metrics["moe"]``), summed over the microbatches; after
the optimizer the step moves each layer's router correction bias by
``bias_update_rate`` towards balance. ``count_step`` hands a step's
expert-row counts to the program's tracing counters.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tracing
from repro.models.model_zoo import Model
from repro.train import grad_compress
from repro.train.optimizer import OptConfig, adamw_update


def _split_microbatches(batch: Dict[str, jax.Array], nmb: int) -> Dict[str, jax.Array]:
    """Microbatch ``m`` takes rows ``m, m + nmb, ...``: strided, so that a
    batch sharded in contiguous row blocks over data-parallel devices gives
    every microbatch rows on every device, and no device waits for another's
    microbatch."""
    def r(x):
        assert x.shape[0] % nmb == 0, f"batch {x.shape[0]} % {nmb} != 0"
        return x.reshape((x.shape[0] // nmb, nmb) + x.shape[1:]).swapaxes(0, 1)

    return jax.tree.map(r, batch)


def make_loss_and_grads(
    model: Model, num_microbatches: int = 1, accum_dtype=jnp.float32
) -> Callable:
    def loss_and_grads(params, batch) -> Tuple[jax.Array, Any, Dict]:
        if num_microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.loss(p, batch), has_aux=True
            )(params)
            return loss, grads, metrics

        mbs = _split_microbatches(batch, num_microbatches)
        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, accum_dtype), params
        )

        def mb_step(carry, mb):
            loss_acc, grads_acc = carry
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.loss(p, mb), has_aux=True
            )(params)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(accum_dtype), grads_acc, grads
            )
            return (loss_acc + loss, grads_acc), metrics

        (loss_sum, grads), metrics = jax.lax.scan(
            mb_step, (jnp.zeros((), jnp.float32), g0), mbs
        )
        inv = 1.0 / num_microbatches
        grads = jax.tree.map(lambda g: (g * inv).astype(jnp.float32), grads)
        last_metrics = {
            k: jax.tree.map(lambda m: m.sum(0) if k == "moe" else m[-1], v)
            for k, v in metrics.items()
        }
        return loss_sum * inv, grads, last_metrics

    return loss_and_grads


def update_router_bias(params: Any, load: Any, rate: float) -> Any:
    """DeepSeek-V3's auxiliary-loss-free balancing: each layer's correction
    bias ``b_i += rate * sign(mean load - load_i)``, from the loads counted
    over the step's tokens (``load`` laid out as ``forward_backbone`` gives
    it)."""
    def moved(b, n):
        step = rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
        return b + step.astype(b.dtype)

    new = dict(params)
    if "blocks" in load:
        new["blocks"] = dict(params["blocks"])
        for name, n in load["blocks"].items():
            layer = dict(new["blocks"][name])
            layer["ffn"] = dict(layer["ffn"],
                                router_bias=moved(layer["ffn"]["router_bias"], n))
            new["blocks"][name] = layer
    if "tail" in load:
        new["tail"] = list(params["tail"])
        for i, n in load["tail"].items():
            layer = dict(new["tail"][int(i)])
            layer["ffn"] = dict(layer["ffn"],
                                router_bias=moved(layer["ffn"]["router_bias"], n))
            new["tail"][int(i)] = layer
    return new


def count_step(metrics: Dict[str, Any]) -> None:
    """While a trace is taken, adds a step's MoE rows to the program's
    counters: ``moe.routed_rows`` (rows the held experts computed, over the
    layers and microbatches), ``moe.expert_rows_max`` (per layer and
    microbatch, the busiest held expert's rows, summed) and
    ``moe.expert_row_groups`` (how many such layer-microbatch groups). Reads
    them back from the device only then."""
    if "moe" not in metrics or not tracing.active():
        return
    m = metrics["moe"]
    tracing.count("moe.routed_rows", int(m["rows"]))
    tracing.count("moe.expert_rows_max", int(m["rows_max"]))
    tracing.count("moe.expert_row_groups", int(m["groups"]))


def make_train_step(
    model: Model,
    opt_cfg: OptConfig,
    *,
    num_microbatches: int = 1,
    accum_dtype=jnp.float32,
    compression: Optional[str] = None,        # None|"bf16"|"int8_ef"
) -> Callable:
    """Returns train_step(params, opt_state, batch[, ef_state]) -> ..."""
    loss_and_grads = make_loss_and_grads(model, num_microbatches, accum_dtype)

    def train_step(params, opt_state, batch, ef_state=None):
        loss, grads, metrics = loss_and_grads(params, batch)
        new_ef = ef_state
        if compression == "bf16":
            # DP all-reduce happens on the bf16 tree (half the pod-axis bytes)
            grads = grad_compress.from_bf16(grad_compress.to_bf16(grads))
        elif compression == "int8_ef":
            assert ef_state is not None
            _, grads, new_ef = grad_compress.ef_compress(grads, ef_state)
        new_params, new_opt, opt_metrics = adamw_update(
            grads, opt_state, params, opt_cfg
        )
        if "moe" in metrics and model.cfg.bias_update_rate:
            new_params = update_router_bias(
                new_params, metrics["moe"]["load"], model.cfg.bias_update_rate)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        if compression == "int8_ef":
            return new_params, new_opt, metrics, new_ef
        return new_params, new_opt, metrics

    return train_step
