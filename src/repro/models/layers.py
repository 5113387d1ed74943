"""Shared neural building blocks (pure-functional JAX, dict pytree params).

Conventions:
  * params live in ``param_dtype`` (fp32), compute casts to ``dtype`` (bf16);
    norms/softmax accumulate in fp32.
  * activation sharding hints are applied through ``shard_act`` which is a
    no-op unless a mesh is active (so the same code runs on 1 CPU device and
    on the 512-device dry-run mesh).
  * batch axes are sharded over ("pod", "data") when present.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BATCH_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"


# -- sharding helpers ----------------------------------------------------------
def _active_axes() -> Tuple[str, ...]:
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return ()
        return tuple(mesh.axis_names)
    except Exception:
        return ()


def batch_spec_axes() -> Optional[Tuple[str, ...]]:
    axes = tuple(a for a in BATCH_AXES if a in _active_axes())
    return axes if axes else None


def shard_act(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that degrades to a no-op without a mesh.

    Spec entries: None, an axis name, "batch" (expands to present batch axes),
    or a tuple of axis names. Unknown axes are dropped.
    """
    axes = _active_axes()
    if not axes:
        return x
    out = []
    for s in spec:
        if s == "batch":
            out.append(batch_spec_axes())
        elif isinstance(s, str):
            out.append(s if s in axes else None)
        elif isinstance(s, tuple):
            keep = tuple(a for a in s if a in axes)
            out.append(keep if keep else None)
        else:
            out.append(None)
    return jax.lax.with_sharding_constraint(x, P(*out))


# -- initializers ----------------------------------------------------------------
def normal_init(key, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def fan_in_init(key, shape, fan_in: int, dtype) -> jax.Array:
    return normal_init(key, shape, fan_in ** -0.5, dtype)


# -- norms ------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype=dtype)}  # gemma-style (1 + scale)


def rmsnorm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype), "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


# -- embeddings / unembedding -------------------------------------------------------
def embed_init(key, vocab: int, d: int, dtype) -> dict:
    return {"table": normal_init(key, (vocab, d), 0.02, dtype)}


def embed_lookup(params: dict, tokens: jax.Array, dtype) -> jax.Array:
    out = jnp.take(params["table"].astype(dtype), tokens, axis=0)
    return shard_act(out, "batch", None, None)


def unembed_logits(params: dict, x: jax.Array, dtype) -> jax.Array:
    """Tied unembedding; logits sharded over vocab (model axis) so the huge
    (B, S, V) tensor never materializes replicated."""
    logits = jnp.einsum("bsd,vd->bsv", x, params["table"].astype(dtype))
    return shard_act(logits, "batch", None, MODEL_AXIS)


# -- dense / MLP ------------------------------------------------------------------
def linear_init(key, d_in: int, d_out: int, dtype, bias: bool = False) -> dict:
    p = {"w": fan_in_init(key, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


def linear(params: dict, x: jax.Array, dtype) -> jax.Array:
    y = x @ params["w"].astype(dtype)
    if "b" in params:
        y = y + params["b"].astype(dtype)
    return y


GLU_ACTS = ("silu", "gelu_glu")   # SwiGLU / GeGLU (gemma-family)


def mlp_init(key, d: int, ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if act in GLU_ACTS:
        return {
            "gate": fan_in_init(ks[0], (d, ff), d, dtype),
            "up": fan_in_init(ks[1], (d, ff), d, dtype),
            "down": fan_in_init(ks[2], (ff, d), ff, dtype),
        }
    return {
        "fc1": fan_in_init(ks[0], (d, ff), d, dtype),
        "fc1_b": jnp.zeros((ff,), dtype=dtype),
        "fc2": fan_in_init(ks[1], (ff, d), ff, dtype),
        "fc2_b": jnp.zeros((d,), dtype=dtype),
    }


def mlp_apply(params: dict, x: jax.Array, act: str, dtype) -> jax.Array:
    if act in GLU_ACTS:
        g = x @ params["gate"].astype(dtype)
        u = x @ params["up"].astype(dtype)
        nl = jax.nn.silu if act == "silu" else jax.nn.gelu
        h = nl(g) * u
        h = shard_act(h, "batch", None, MODEL_AXIS)
        return h @ params["down"].astype(dtype)
    h = x @ params["fc1"].astype(dtype) + params["fc1_b"].astype(dtype)
    h = jax.nn.gelu(h)
    h = shard_act(h, "batch", None, MODEL_AXIS)
    return h @ params["fc2"].astype(dtype) + params["fc2_b"].astype(dtype)


# -- rotary position embeddings -----------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def rope_angles(
    positions: jax.Array,          # (B, S) int or (B, S, 3) for M-RoPE
    head_dim: int,
    theta: float,
    mrope_sections: Sequence[int] = (),
) -> Tuple[jax.Array, jax.Array]:
    """Returns (cos, sin), each (B, S, head_dim//2), fp32.

    M-RoPE (Qwen2-VL, arXiv:2409.12191): the rotary frequency dims are split
    into (t, h, w) sections; each section takes its angle from the matching
    coordinate of the 3-D position ids.
    """
    freqs = rope_freqs(head_dim, theta)                       # (half,)
    if positions.ndim == 3 and mrope_sections:
        assert sum(mrope_sections) == head_dim // 2, (
            f"mrope sections {mrope_sections} != head_dim/2 {head_dim//2}"
        )
        pos = positions.astype(jnp.float32)                   # (B, S, 3)
        parts = []
        start = 0
        for sec_idx, sec in enumerate(mrope_sections):
            f = freqs[start : start + sec]                     # (sec,)
            ang = pos[..., sec_idx : sec_idx + 1] * f          # (B, S, sec)
            parts.append(ang)
            start += sec
        angles = jnp.concatenate(parts, axis=-1)               # (B, S, half)
    else:
        pos = positions.astype(jnp.float32)                    # (B, S)
        angles = pos[..., None] * freqs                        # (B, S, half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2). Rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array
                           ) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2). DeepSeek-V3's layout: pair
    i is (x[2i], x[2i+1]). The result holds the rotated even members, then
    the odd ones; a dot product of two vectors rotated alike does not depend
    on that order."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# -- loss --------------------------------------------------------------------------
def softmax_xent(
    logits: jax.Array,      # (B, S, V) — possibly vocab-sharded
    labels: jax.Array,      # (B, S) int32
    valid: Optional[jax.Array] = None,
    mode: str = "gather",
) -> jax.Array:
    """Mean cross-entropy in fp32. Works with vocab-sharded logits: max/sum
    reductions over the vocab axis become cross-shard collectives under SPMD.

    ``mode``:
      * "gather" — take_along_axis for the gold logit. Simple, but indexing a
        vocab-sharded axis makes SPMD all-gather the full (B, S, V) logits —
        measured 12.7 s of collective time on phi4-mini train (§Perf).
      * "onehot" — gold logit via a masked reduction over the (sharded) vocab
        axis; reduces with a cheap all-reduce of (B, S) instead.
    """
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    shifted = lf - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    if mode == "onehot":
        V = logits.shape[-1]
        hit = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, 2)
               == labels[..., None])
        gold = jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
    else:
        gold = jnp.take_along_axis(shifted, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if valid is not None:
        v = valid.astype(jnp.float32)
        return jnp.sum(nll * v) / jnp.maximum(jnp.sum(v), 1.0)
    return jnp.mean(nll)
