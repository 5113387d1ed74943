"""AdamW + warmup-cosine schedule, from scratch (no optax).

Moments are fp32 pytrees mirroring params. ZeRO-1 is realized at the jit
boundary: ``launch/sharding.py`` assigns the moment trees a sharding that
adds the ``data`` axis on top of each param's ``model``-axis sharding, so
optimizer state is fully distributed (27B-param models fit v5e HBM only
because of this — see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def lr_at(cfg: OptConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = cfg.peak_lr * jnp.minimum(s / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip(
        (s - cfg.warmup_steps) / jnp.maximum(cfg.decay_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(s < cfg.warmup_steps, warm, cfg.peak_lr * cos)


def init_opt_state(params: Any, master_weights: bool = False) -> Dict[str, Any]:
    """``master_weights=True``: keep fp32 master copies in the (ZeRO-sharded)
    optimizer state so model params can live in bf16 — halves the resident
    param bytes per chip; the masters are sharded over data×model."""
    state = {
        "mu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "step": jnp.zeros((), jnp.int32),
    }
    if master_weights:
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params
        )
    return state


def global_norm(tree: Any) -> jax.Array:
    sq = sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)
    )
    return jnp.sqrt(sq)


FROZEN_LEAVES = ("router_bias",)


def is_frozen(path) -> bool:
    """Leaves that AdamW and weight decay leave alone: each is updated by a
    rule of its own (the router's correction bias, ``train_step``)."""
    last = path[-1] if path else None
    return getattr(last, "key", None) in FROZEN_LEAVES


def adamw_update(
    grads: Any,
    opt_state: Dict[str, Any],
    params: Any,
    cfg: OptConfig,
) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
        if cfg.grad_clip > 0 else jnp.ones(())
    lr = lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, mu, nu, master):
        ref = master if master is not None else p.astype(jnp.float32)
        g = g.astype(jnp.float32) * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        mhat = mu / bc1
        nhat = nu / bc2
        delta = mhat / (jnp.sqrt(nhat) + cfg.eps) + cfg.weight_decay * ref
        new_master = ref - lr * delta
        return new_master.astype(p.dtype), mu, nu, new_master

    def held(p, g, mu, nu, master):
        return p, mu, nu, master

    has_master = "master" in opt_state
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_p = [p for _, p in paths]
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(opt_state["mu"])
    flat_nu = treedef.flatten_up_to(opt_state["nu"])
    flat_ma = (treedef.flatten_up_to(opt_state["master"]) if has_master
               else [None] * len(flat_p))
    out = [(held if is_frozen(path) else upd)(p, g, m, n, ma)
           for (path, p), g, m, n, ma
           in zip(paths, flat_g, flat_mu, flat_nu, flat_ma)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_state = {
        "mu": jax.tree.unflatten(treedef, [o[1] for o in out]),
        "nu": jax.tree.unflatten(treedef, [o[2] for o in out]),
        "step": step,
    }
    if has_master:
        new_state["master"] = jax.tree.unflatten(treedef, [o[3] for o in out])
    return new_params, new_state, {"lr": lr, "grad_norm": gnorm}
