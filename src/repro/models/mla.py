"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 §2.1.1),
without query compression.

For ``h`` the normed input of a layer:

  q = h W_q                          H heads of qk_nope + qk_rope
  [c, k_pe] = h W_kva                c of kv_lora_rank, one k_pe of qk_rope
  c = RMSNorm(c)
  [k_nope, v] = c W_kvb              per head qk_nope + v_head_dim
  RoPE on q_pe and k_pe (interleaved pairs); k_pe is shared by all heads
  out = concat_h(softmax((q_nope.k_nope + q_pe.k_pe) / sqrt(qk_nope +
        qk_rope)) v) W_o             causal

Training and prefill expand the latent into per-head keys and values and
use the q-chunked attention of ``attention.py``. Decode keeps only the
latent cache, ``kv_lora_rank + qk_rope`` values per token, and absorbs
``W_kvb`` into the query and the output.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import NEG_INF, _chunked_sdpa, _sdpa, causal_window_mask
from repro.models.layers import (
    MODEL_AXIS,
    apply_rope_interleaved,
    fan_in_init,
    rmsnorm,
    rmsnorm_init,
    shard_act,
)


class MLACache(NamedTuple):
    c: jax.Array          # (B, C, kv_lora_rank), normed latent
    k_pe: jax.Array       # (B, C, qk_rope), rotated
    slot_pos: jax.Array   # (C,) int32, absolute position in slot (-1 empty)


def init_mla_cache(batch: int, capacity: int, rank: int, rope: int,
                   dtype) -> MLACache:
    return MLACache(
        c=jnp.zeros((batch, capacity, rank), dtype=dtype),
        k_pe=jnp.zeros((batch, capacity, rope), dtype=dtype),
        slot_pos=jnp.full((capacity,), -1, dtype=jnp.int32),
    )


def mla_init(key, d: int, heads: int, rank: int, nope: int, rope: int,
             v_dim: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": fan_in_init(ks[0], (d, heads, nope + rope), d, dtype),
        "wkv_a": fan_in_init(ks[1], (d, rank + rope), d, dtype),
        "kv_norm": rmsnorm_init(rank, dtype),
        "wkv_b": fan_in_init(ks[2], (rank, heads, nope + v_dim), rank, dtype),
        "wo": fan_in_init(ks[3], (heads, v_dim, d), heads * v_dim, dtype),
    }


def _project(params: dict, x, cos, sin, *, rank: int, nope: int, dtype,
             eps: float):
    """(q_nope, q_pe, c, k_pe): q parts (B, S, H, .), the normed latent
    (B, S, rank) and the rotated shared key (B, S, rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kva = x @ params["wkv_a"].astype(dtype)
    c = rmsnorm(params["kv_norm"], kva[..., :rank], eps)
    k_pe = apply_rope_interleaved(kva[..., None, rank:], cos, sin)[:, :, 0]
    q_pe = apply_rope_interleaved(q_pe, cos, sin)
    return q_nope, q_pe, c, k_pe


def mla_train(params: dict, x: jax.Array, cos: jax.Array, sin: jax.Array, *,
              rank: int, nope: int, dtype, eps: float, q_chunk: int = 0
              ) -> jax.Array:
    """Causal MLA over a whole sequence (training and prefill)."""
    with jax.named_scope("mla"):
        q_nope, q_pe, c, k_pe = _project(params, x, cos, sin, rank=rank,
                                         nope=nope, dtype=dtype, eps=eps)
        kv = jnp.einsum("bsr,rhk->bshk", c, params["wkv_b"].astype(dtype))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        H = q_nope.shape[2]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], k_pe.shape[:2] + (H,)
                                      + k_pe.shape[-1:])], axis=-1)
        q = shard_act(q, "batch", None, MODEL_AXIS, None)
        k = shard_act(k, "batch", None, MODEL_AXIS, None)
        v = shard_act(v, "batch", None, MODEL_AXIS, None)
        S = x.shape[1]
        if q_chunk and S > q_chunk and S % q_chunk == 0:
            out = _chunked_sdpa(q, k, v, causal=True, window=0, softcap=0.0,
                                q_chunk=q_chunk)
        else:
            out = _sdpa(q, k, v, mask=causal_window_mask(S, S, 0))
        out = shard_act(out, "batch", None, MODEL_AXIS, None)
        return jnp.einsum("bshv,hvd->bsd", out, params["wo"].astype(dtype))


def mla_decode(params: dict, x: jax.Array, cache: MLACache, pos: jax.Array,
               cos: jax.Array, sin: jax.Array, *, rank: int, nope: int, dtype,
               eps: float) -> Tuple[jax.Array, MLACache]:
    """One new token per sequence against the latent cache, with ``W_kvb``
    absorbed: scores from ``q_nope W_kb^T . c`` and ``q_pe . k_pe``, values
    from ``(probs . c) W_vb``."""
    with jax.named_scope("mla"):
        q_nope, q_pe, c_new, k_pe_new = _project(
            params, x, cos, sin, rank=rank, nope=nope, dtype=dtype, eps=eps)
        C = cache.c.shape[1]
        slot = jnp.mod(pos, C)
        c = jax.lax.dynamic_update_slice(
            cache.c, c_new.astype(cache.c.dtype), (0, slot, 0))
        k_pe = jax.lax.dynamic_update_slice(
            cache.k_pe, k_pe_new.astype(cache.k_pe.dtype), (0, slot, 0))
        slot_pos = jax.lax.dynamic_update_slice(
            cache.slot_pos, pos.astype(jnp.int32)[None], (slot,))
        wkv_b = params["wkv_b"].astype(dtype)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wkv_b[..., :nope])
        scale = (nope + q_pe.shape[-1]) ** -0.5
        scores = (jnp.einsum("bqhr,bcr->bhqc", q_lat, c.astype(dtype))
                  + jnp.einsum("bqhp,bcp->bhqc", q_pe, k_pe.astype(dtype)))
        scores = scores.astype(jnp.float32) * scale
        ok = (slot_pos >= 0) & (slot_pos <= pos)
        scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        o_lat = jnp.einsum("bhqc,bcr->bqhr", probs, c.astype(dtype))
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat, wkv_b[..., nope:])
        out = jnp.einsum("bqhv,hvd->bqd", out, params["wo"].astype(dtype))
        return out, MLACache(c=c, k_pe=k_pe, slot_pos=slot_pos)
