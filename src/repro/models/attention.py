"""Attention: GQA/MQA/MHA with causal, sliding-window, and bidirectional
masking; unified ring-buffer KV cache for decode.

The XLA-native path here is the dry-run / reference implementation; the
Pallas ``flash_attention`` kernel in ``repro.kernels`` implements the same
math with VMEM tiling for the TPU target (validated against this module's
``_sdpa`` oracle in the kernel tests).

Ring-buffer KV cache: every attention layer stores k/v of capacity C =
``window`` (local layers) or ``seq_len`` budget (global layers), plus the
absolute position of each slot. A decode step writes slot ``pos % C`` and
masks by slot position, so local layers hold O(window) memory — the reason
recurrentgemma/gemma3 long-context decode stays cheap.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (
    MODEL_AXIS,
    apply_rope,
    fan_in_init,
    rmsnorm,
    rmsnorm_init,
    shard_act,
)

NEG_INF = -2.0e38


class KVCache(NamedTuple):
    k: jax.Array          # (B, C, K, hd)
    v: jax.Array          # (B, C, K, hd)
    slot_pos: jax.Array   # (C,) int32, absolute position stored in slot (-1 empty)


def init_cache(batch: int, capacity: int, kv_heads: int, head_dim: int,
               dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, kv_heads, head_dim), dtype=dtype),
        v=jnp.zeros((batch, capacity, kv_heads, head_dim), dtype=dtype),
        slot_pos=jnp.full((capacity,), -1, dtype=jnp.int32),
    )


def attn_init(key, d: int, heads: int, kv_heads: int, head_dim: int, dtype,
              bias: bool = False, qk_norm: bool = False,
              phys_heads: int = 0, phys_kv: int = 0) -> dict:
    """``phys_heads``/``phys_kv`` pad (H, K) to TP-divisible physical counts
    with the same G = H/K (e.g. phi4's (24, 8) -> (48, 16)). Padded slices
    are zero-initialized; since padded q/k/v project to zero, their attention
    output is exactly zero and all gradients into padded slices vanish — the
    padded model is bit-exact with the real one."""
    H = phys_heads or heads
    K = phys_kv or kv_heads
    if phys_heads or phys_kv:
        assert H // K == heads // kv_heads and H % K == 0, (
            f"padding must preserve the GQA ratio: ({heads},{kv_heads}) -> "
            f"({H},{K})"
        )
    ks = jax.random.split(key, 4)
    p = {
        "wq": fan_in_init(ks[0], (d, H, head_dim), d, dtype),
        "wk": fan_in_init(ks[1], (d, K, head_dim), d, dtype),
        "wv": fan_in_init(ks[2], (d, K, head_dim), d, dtype),
        "wo": fan_in_init(ks[3], (H, head_dim, d), heads * head_dim, dtype),
    }
    if H > heads:
        p["wq"] = p["wq"].at[:, heads:].set(0.0)
        p["wo"] = p["wo"].at[heads:].set(0.0)
    if K > kv_heads:
        p["wk"] = p["wk"].at[:, kv_heads:].set(0.0)
        p["wv"] = p["wv"].at[:, kv_heads:].set(0.0)
    if bias:
        p["bq"] = jnp.zeros((H, head_dim), dtype=dtype)
        p["bk"] = jnp.zeros((K, head_dim), dtype=dtype)
        p["bv"] = jnp.zeros((K, head_dim), dtype=dtype)
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim, dtype)
        p["k_norm"] = rmsnorm_init(head_dim, dtype)
    return p


def _project_qkv(params: dict, x: jax.Array, dtype, eps: float
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    if "q_norm" in params:
        q = rmsnorm(params["q_norm"], q, eps)
        k = rmsnorm(params["k_norm"], k, eps)
    return q, k, v


def _sdpa(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, K, hd)
    v: jax.Array,            # (B, Sk, K, hd)
    *,
    mask: Optional[jax.Array],   # broadcastable to (B, K, G, Sq, Sk) or None
    softcap: float = 0.0,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * scale
    scores = scores.astype(jnp.float32)
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def causal_window_mask(sq: int, sk: int, window: int, offset: int = 0) -> jax.Array:
    """(1,1,1,Sq,Sk) boolean: j <= i+offset and (window==0 or i+offset-j < window)."""
    i = jnp.arange(sq)[:, None] + offset
    j = jnp.arange(sk)[None, :]
    m = j <= i
    if window > 0:
        m &= (i - j) < window
    return m[None, None, None]


def _chunked_sdpa(q, k, v, *, causal: bool, window: int, softcap: float,
                  q_chunk: int) -> jax.Array:
    """q-chunked attention: bounds the live score tensor to
    (B, K, G, q_chunk, S); each chunk is rematerialized in the backward pass
    (jax.checkpoint), so activation memory is one chunk — the XLA-native
    equivalent of flash attention's memory behaviour (FLOPs unchanged)."""
    B, S, H, hd = q.shape
    nc = S // q_chunk

    @jax.checkpoint
    def one_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        qpos = i * q_chunk + jnp.arange(q_chunk)[:, None]
        kpos = jnp.arange(S)[None, :]
        m = jnp.ones((q_chunk, S), bool)
        if causal:
            m &= kpos <= qpos
        if window > 0:
            m &= (qpos - kpos) < window
        return _sdpa(qs, k, v, mask=m[None, None, None], softcap=softcap)

    out = jax.lax.map(one_chunk, jnp.arange(nc))       # (nc, B, qc, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def attention_train(
    params: dict,
    x: jax.Array,                  # (B, S, d)
    cos: jax.Array, sin: jax.Array,
    *,
    dtype,
    eps: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    use_rope: bool = True,
    q_chunk: int = 0,
) -> jax.Array:
    q, k, v = _project_qkv(params, x, dtype, eps)
    if use_rope:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard_act(q, "batch", None, MODEL_AXIS, None)
    k = shard_act(k, "batch", None, MODEL_AXIS, None)
    v = shard_act(v, "batch", None, MODEL_AXIS, None)
    S = x.shape[1]
    if q_chunk and S > q_chunk and S % q_chunk == 0 and causal:
        out = _chunked_sdpa(q, k, v, causal=causal, window=window,
                            softcap=softcap, q_chunk=q_chunk)
    else:
        mask = causal_window_mask(S, S, window) if causal else None
        out = _sdpa(q, k, v, mask=mask, softcap=softcap)
    out = shard_act(out, "batch", None, MODEL_AXIS, None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


def cross_attention(
    params: dict,
    x: jax.Array,                  # (B, Sq, d) decoder side
    kv_src: Tuple[jax.Array, jax.Array],   # precomputed (k, v): (B, Sk, K, hd)
    *,
    dtype,
) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
    k, v = kv_src
    out = _sdpa(q, k, v, mask=None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


def cross_kv(params: dict, enc: jax.Array, dtype) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", enc, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc, params["wv"].astype(dtype))
    if "bk" in params:
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return k, v


def attention_decode(
    params: dict,
    x: jax.Array,                  # (B, 1, d) new token
    cache: KVCache,
    pos: jax.Array,                # scalar int32: absolute position of the new token
    cos: jax.Array, sin: jax.Array,  # (B, 1, hd//2) for the new position
    *,
    dtype,
    eps: float,
    window: int = 0,
    softcap: float = 0.0,
    use_rope: bool = True,
) -> Tuple[jax.Array, KVCache]:
    q, k_new, v_new = _project_qkv(params, x, dtype, eps)
    if use_rope:
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)
    C = cache.k.shape[1]
    slot = jnp.mod(pos, C)
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                     (0, slot, 0, 0))
    slot_pos = jax.lax.dynamic_update_slice(
        cache.slot_pos, pos.astype(jnp.int32)[None], (slot,)
    )
    # mask by absolute slot position: valid, <= pos, and within window
    ok = (slot_pos >= 0) & (slot_pos <= pos)
    if window > 0:
        ok &= (pos - slot_pos) < window
    mask = ok[None, None, None, None, :]       # (1,1,1,1,C)
    out = _sdpa(q, k, v, mask=mask, softcap=softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))
    return out, KVCache(k=k, v=v, slot_pos=slot_pos)


def prefill_cache(
    params: dict,
    x: jax.Array,                  # (B, S, d)
    cos: jax.Array, sin: jax.Array,
    capacity: int,
    *,
    dtype,
    eps: float,
    use_rope: bool = True,
) -> KVCache:
    """Build a decode cache from a full prefill pass (keeps last `capacity`)."""
    _, k, v = _project_qkv(params, x, dtype, eps)
    if use_rope:
        k = apply_rope(k, cos, sin)
    B, S = x.shape[:2]
    if capacity >= S:
        pad = capacity - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        slot_pos = jnp.concatenate(
            [jnp.arange(S, dtype=jnp.int32),
             jnp.full((pad,), -1, dtype=jnp.int32)]
        )
    else:
        kc = k[:, S - capacity:]
        vc = v[:, S - capacity:]
        slot_pos = jnp.arange(S - capacity, S, dtype=jnp.int32)
        # ring alignment: slot index = pos % capacity
        roll = (S - capacity) % capacity
        kc = jnp.roll(kc, roll, axis=1)
        vc = jnp.roll(vc, roll, axis=1)
        slot_pos = jnp.roll(slot_pos, roll)
    return KVCache(k=kc.astype(dtype), v=vc.astype(dtype), slot_pos=slot_pos)
