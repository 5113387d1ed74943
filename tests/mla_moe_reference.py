"""Plain reference of the DeepSeek-V3-style decoder (Moonlight-16B-A3B):
leading dense layers, then layers of multi-head latent attention and a
sigmoid-routed MoE with shared experts; the loss and each expert's load.

Written from the layer equations (arXiv:2412.19437 §2.1) in straightforward
``jax.numpy``: float32, every matmul at HIGHEST precision, the whole batch at
once, no kernel, cache or dispatch. Each held expert runs on every token and
is weighted by its gate, 0 where the token did not choose it. It reads the
program's parameter tree and the sizes of a ``ModelConfig``, and uses
nothing else of the program. Norms are applied as ``x * rsqrt(mean(x^2) +
eps) * (1 + scale)``, as the program holds them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def mm(spec, *ops):
    return jnp.einsum(spec, *ops, precision=lax.Precision.HIGHEST)


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + scale)


def rope(x, theta):
    """x (B, S, N, r): pair i is (x[2i], x[2i+1]), rotated by position x
    theta^(-2i/r); evens then odds out."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(h, p, cfg):
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = mm("bsd,dhk->bshk", h, p["wq"])
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], cfg.rope_theta)
    kva = mm("bsd,dr->bsr", h, p["wkv_a"])
    c = rms(kva[..., :rank], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope(kva[..., None, rank:], cfg.rope_theta)
    kv = mm("bsr,rhk->bshk", c, p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (mm("bqhn,bkhn->bhqk", q_nope, k_nope)
              + mm("bqhr,bkxr->bhqk", q_pe, k_pe))
    scores = scores / jnp.sqrt(float(nope + cfg.qk_rope_head_dim))
    S = h.shape[1]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return mm("bqhv,hvd->bqd", mm("bhqk,bkhv->bqhv", probs, v), p["wo"])


def swiglu(h, gate, up, down):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", h, gate))
              * mm("bsd,df->bsf", h, up), down)


def route(h, p, cfg):
    """(gates of every expert (B, S, E), chosen experts (B, S, k), scores)."""
    scores = jax.nn.sigmoid(mm("bsd,de->bse", h, p["router"]))
    _, idx = lax.top_k(scores + lax.stop_gradient(p["router_bias"]), cfg.top_k)
    chosen = jnp.take_along_axis(scores, idx, -1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * cfg.routed_scaling
    E = scores.shape[-1]
    gates = jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], axis=-2)
    return gates, idx, scores


def moe(h, p, cfg):
    """(held experts' part plus the shared experts, balance loss, loads)."""
    gates, idx, scores = route(h, p, cfg)
    held = p["gate"].shape[0]
    out = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in range(held):
        g = gates[..., cfg.first_expert + e, None]
        out = out + g * swiglu(h, p["gate"][e], p["up"][e], p["down"][e])
    E = scores.shape[-1]
    B, S, k = idx.shape
    count = jnp.sum(jax.nn.one_hot(idx, E), axis=(1, 2))            # (B, E)
    f = count * E / (k * S)
    share = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    aux = jnp.mean(jnp.sum(f * share, -1))
    return out, aux, jnp.sum(count, 0)


def layer(x, p, cfg, dense: bool):
    x = x + mla(rms(x, p["norm1"]["scale"], cfg.norm_eps), p["mixer"], cfg)
    h = rms(x, p["norm2"]["scale"], cfg.norm_eps)
    if dense:
        f = p["ffn"]
        return x + swiglu(h, f["gate"], f["up"], f["down"]), 0.0, None
    out, aux, load = moe(h, p["ffn"], cfg)
    return x + out, aux, load


def loss_and_loads(params, cfg, tokens, labels):
    """Mean cross-entropy plus ``router_aux_coef`` x the layers' balance
    losses, and each MoE layer's loads laid out as the program's ``load``
    (``blocks`` stacked, for a one-layer block pattern)."""
    x = params["embed"]["table"][tokens]
    aux_total = 0.0
    for p in params.get("lead", []):
        x, _, _ = layer(x, p, cfg, dense=True)
    loads = []
    blocks = params["blocks"]["l0"]
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        x, aux, load = layer(x, jax.tree.map(lambda a: a[i], blocks), cfg,
                             dense=False)
        aux_total = aux_total + aux
        loads.append(load)
    x = rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = mm("bsd,dv->bsv", x, params["lm_head"]["w"])
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss = jnp.mean(lse - gold) + cfg.router_aux_coef * aux_total
    return loss, {"blocks": {"l0": jnp.stack(loads)}}
