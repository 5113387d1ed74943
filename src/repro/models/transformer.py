"""Decoder-only transformer stack assembly (all non-enc-dec architectures).

Layers are grouped into repeating blocks (``cfg.block_pattern``); the stack
``lax.scan``s over whole blocks (stacked params) and unrolls the remainder —
HLO size stays O(pattern), not O(num_layers), which keeps 62-layer models
compilable and lets remat apply per block. Heterogeneous patterns (gemma3's
5 local : 1 global, griffin's 2 recurrent : 1 attn) are python-static inside
the block function, so no lax.cond is needed.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN,
    ATTN_LOCAL,
    DENSE,
    MAMBA,
    MLA,
    MOE,
    NONE,
    RGLRU,
    ModelConfig,
)
from repro.models import attention as attn_mod
from repro.models.attention import KVCache, attn_init, init_cache
from repro.models.layers import (
    embed_init,
    embed_lookup,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    rope_angles,
    shard_act,
    softmax_xent,
    unembed_logits,
)
from repro.models.mla import init_mla_cache, mla_decode, mla_init, mla_train
from repro.models.moe import moe_apply, moe_apply_dropless, moe_init
from repro.models.rglru import (
    RGLRUState,
    rglru_apply,
    rglru_decode,
    rglru_init,
    rglru_init_state,
)
from repro.models.ssm import (
    MambaState,
    mamba_apply,
    mamba_decode,
    mamba_init,
    mamba_init_state,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_layer(key, cfg: ModelConfig, spec) -> Dict[str, Any]:
    pd = _pdtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(d, pd)}
    if spec.mixer in (ATTN, ATTN_LOCAL):
        p["mixer"] = attn_init(
            ks[0], d, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            pd, bias=cfg.attn_bias, qk_norm=cfg.qk_norm,
            phys_heads=cfg.num_heads_phys, phys_kv=cfg.num_kv_heads_phys,
        )
    elif spec.mixer == MLA:
        p["mixer"] = mla_init(
            ks[0], d, cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, pd,
        )
    elif spec.mixer == MAMBA:
        p["mixer"] = mamba_init(
            ks[0], d, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_width, pd
        )
    elif spec.mixer == RGLRU:
        p["mixer"] = rglru_init(ks[0], d, cfg.lru_width, cfg.conv_width, pd)
    if spec.ffn != NONE:
        p["norm2"] = rmsnorm_init(d, pd)
        if spec.ffn == DENSE:
            p["ffn"] = mlp_init(ks[1], d, cfg.d_ff, cfg.act, pd)
        else:
            p["ffn"] = moe_init(
                ks[1], d, cfg.num_experts, cfg.moe_d_ff,
                cfg.num_shared_experts, pd, expert_pad=cfg.expert_pad,
                held=cfg.experts_held,
                router_bias=cfg.router_score == "sigmoid",
            )
    return p


def init_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    pattern = cfg.block_pattern
    ks = jax.random.split(key, len(pattern))
    return {f"l{i}": init_layer(ks[i], cfg, spec)
            for i, spec in enumerate(pattern)}


def init_model(key, cfg: ModelConfig) -> Dict[str, Any]:
    pd = _pdtype(cfg)
    pattern, nb, tail = cfg.scan_split()
    lead = cfg.lead_layers()
    n_keys = 2 + nb + len(tail) + (0 if cfg.tie_embeddings else 1) + len(lead)
    ks = jax.random.split(key, n_keys)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, pd),
        "final_norm": rmsnorm_init(cfg.d_model, pd),
    }
    if lead:
        params["lead"] = [init_layer(ks[n_keys - len(lead) + i], cfg, spec)
                          for i, spec in enumerate(lead)]
    if nb > 0:
        params["blocks"] = jax.vmap(lambda k: init_block(k, cfg))(
            jnp.stack(ks[2 : 2 + nb])
        )
    params["tail"] = [
        init_layer(ks[2 + nb + i], cfg, spec) for i, spec in enumerate(tail)
    ]
    if not cfg.tie_embeddings:
        from repro.models.layers import fan_in_init

        params["lm_head"] = {
            "w": fan_in_init(ks[2 + nb + len(tail)],
                             (cfg.d_model, cfg.vocab_size), cfg.d_model, pd)
        }
    return params


# ---------------------------------------------------------------------------
# train / prefill forward
# ---------------------------------------------------------------------------
def _ffn(params, spec, cfg: ModelConfig, h):
    """The layer's FFN on ``h``: (output, aux loss, MoE stats or None)."""
    dt = _dtype(cfg)
    if spec.ffn == DENSE:
        return mlp_apply(params, h, cfg.act, dt), jnp.zeros((), jnp.float32), None
    if cfg.router_score == "sigmoid":
        return moe_apply_dropless(
            params, h, top_k=cfg.top_k, scaling=cfg.routed_scaling,
            first=cfg.first_expert, dtype=dt,
        )
    f, aux = moe_apply(
        params, h, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        dtype=dt, num_real_experts=cfg.num_experts,
    )
    return f, aux, None


def apply_layer_train(params, spec, cfg: ModelConfig, x, cos, sin):
    """One layer: (output, aux loss, the MoE layer's stats or None)."""
    dt = _dtype(cfg)
    eps = cfg.norm_eps
    h = rmsnorm(params["norm1"], x, eps)
    if spec.mixer == MLA:
        m = mla_train(
            params["mixer"], h, cos, sin, rank=cfg.kv_lora_rank,
            nope=cfg.qk_nope_head_dim, dtype=dt, eps=eps,
            q_chunk=cfg.attn_q_chunk,
        )
    elif spec.mixer in (ATTN, ATTN_LOCAL):
        m = attn_mod.attention_train(
            params["mixer"], h, cos, sin, dtype=dt, eps=eps, causal=True,
            window=spec.window, softcap=cfg.attn_logit_softcap,
            use_rope=cfg.use_rope, q_chunk=cfg.attn_q_chunk,
        )
    elif spec.mixer == MAMBA:
        m = mamba_apply(params["mixer"], h, dtype=dt, chunk=cfg.ssm_chunk,
                        impl=cfg.ssm_impl)
    elif spec.mixer == RGLRU:
        m = rglru_apply(params["mixer"], h, dtype=dt)
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    x = x + m
    aux = jnp.zeros((), jnp.float32)
    stats = None
    if spec.ffn != NONE:
        h = rmsnorm(params["norm2"], x, eps)
        f, aux, stats = _ffn(params["ffn"], spec, cfg, h)
        x = x + f
    x = shard_act(x, "batch", None, None)
    return x, aux, stats


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    raise ValueError(f"unknown remat policy {policy!r}")


def forward_backbone(params, cfg: ModelConfig, x, cos, sin
                     ) -> Tuple[jax.Array, jax.Array, Optional[dict]]:
    """(final hidden states, summed aux loss, MoE stats or None). The stats
    of the dropless MoE layers: ``load``, each expert's tokens, laid out as
    the layers' ``router_bias`` leaves (``blocks``: ``l<i>`` stacked over
    blocks; ``tail``: by layer index), and ``rows``, ``rows_max`` and
    ``groups``, summed over the layers."""
    pattern, nb, tail = cfg.scan_split()
    aux_total = jnp.zeros((), jnp.float32)
    loads: Dict[str, Any] = {}
    per_layer = []

    for i, spec in enumerate(cfg.lead_layers()):
        lead_fn = _remat(
            lambda p, h, spec=spec: apply_layer_train(p, spec, cfg, h, cos,
                                                      sin)[:2],
            cfg.remat_policy)
        x, a = lead_fn(params["lead"][i], x)
        aux_total = aux_total + a
    if nb > 0:
        def block_fn(carry, bp):
            h, aux = carry
            block_stats = {}
            for i, spec in enumerate(pattern):
                h, a, st = apply_layer_train(bp[f"l{i}"], spec, cfg, h, cos,
                                             sin)
                aux = aux + a
                if st is not None:
                    block_stats[f"l{i}"] = st
            return (h, aux), (block_stats or None)

        block_fn = _remat(block_fn, cfg.remat_policy)
        if cfg.scan_layers:
            (x, aux_total), block_stats = jax.lax.scan(
                block_fn, (x, aux_total), params["blocks"]
            )
        else:
            ys = []
            for bi in range(nb):
                bp = jax.tree.map(lambda p: p[bi], params["blocks"])
                (x, aux_total), y = block_fn((x, aux_total), bp)
                ys.append(y)
            block_stats = (None if ys[0] is None else
                           jax.tree.map(lambda *a: jnp.stack(a), *ys))
        if block_stats is not None:
            loads["blocks"] = {k: v["load"] for k, v in block_stats.items()}
            per_layer += list(block_stats.values())
    for i, spec in enumerate(tail):
        x, a, st = apply_layer_train(params["tail"][i], spec, cfg, x, cos, sin)
        aux_total = aux_total + a
        if st is not None:
            loads.setdefault("tail", {})[str(i)] = st["load"]
            per_layer.append(st)
    stats = None
    if per_layer:
        stats = {
            "load": loads,
            "rows": sum(jnp.sum(s["rows"]) for s in per_layer),
            "rows_max": sum(jnp.sum(s["rows_max"]) for s in per_layer),
            "groups": jnp.asarray(sum(jnp.size(s["rows"]) for s in per_layer),
                                  jnp.int32),
        }
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux_total, stats


def _positions(cfg: ModelConfig, batch: Dict[str, jax.Array], S: int, B: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


def _input_x(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    dt = _dtype(cfg)
    if cfg.input_mode == "embeddings" and "embeds" in batch:
        x = batch["embeds"].astype(dt)
        x = shard_act(x, "batch", None, None)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed_lookup(params["embed"], tokens, dt)
    return x, B, S


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full train forward -> (scalar loss fp32, metrics)."""
    x, B, S = _input_x(params, cfg, batch)
    pos = _positions(cfg, batch, S, B)
    cos, sin = rope_angles(pos, cfg.rope_dim, cfg.rope_theta,
                           cfg.mrope_sections)
    x, aux, stats = forward_backbone(params, cfg, x, cos, sin)
    if cfg.tie_embeddings:
        logits = unembed_logits(params["embed"], x, _dtype(cfg))
    else:
        logits = x @ params["lm_head"]["w"].astype(_dtype(cfg))
        logits = shard_act(logits, "batch", None, "model")
    xent = softmax_xent(logits, batch["labels"], mode=cfg.xent_mode)
    loss = xent + cfg.router_aux_coef * aux
    metrics = {"xent": xent, "aux": aux}
    if stats is not None:
        metrics["moe"] = jax.tree.map(jax.lax.stop_gradient, stats)
    return loss, metrics


def forward_logits(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                   last_only: bool = True) -> jax.Array:
    """Prefill forward (no labels). Returns last-position logits by default."""
    x, B, S = _input_x(params, cfg, batch)
    pos = _positions(cfg, batch, S, B)
    cos, sin = rope_angles(pos, cfg.rope_dim, cfg.rope_theta,
                           cfg.mrope_sections)
    x, _, _ = forward_backbone(params, cfg, x, cos, sin)
    if last_only:
        x = x[:, -1:]
    if cfg.tie_embeddings:
        return unembed_logits(params["embed"], x, _dtype(cfg))
    return x @ params["lm_head"]["w"].astype(_dtype(cfg))


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
class DecodeState(NamedTuple):
    blocks: Any            # per-pattern-position states, stacked over nb
    tail: Any              # list of per-layer states
    pos: jax.Array         # scalar int32: next absolute position
    lead: Any = ()         # states of the leading dense layers


def _layer_capacity(cfg: ModelConfig, spec, seq_budget: int) -> int:
    if spec.mixer == ATTN_LOCAL and spec.window > 0:
        return min(spec.window, seq_budget)
    return seq_budget


def init_layer_state(cfg: ModelConfig, spec, batch: int, seq_budget: int):
    dt = _dtype(cfg)
    if spec.mixer == MLA:
        return init_mla_cache(batch, seq_budget, cfg.kv_lora_rank,
                              cfg.qk_rope_head_dim, dt)
    if spec.mixer in (ATTN, ATTN_LOCAL):
        return init_cache(
            batch, _layer_capacity(cfg, spec, seq_budget),
            cfg.num_kv_heads_phys or cfg.num_kv_heads,
            cfg.resolved_head_dim, dt,
        )
    if spec.mixer == MAMBA:
        di, n = cfg.d_inner, cfg.ssm_state
        return MambaState(
            h=jnp.zeros((batch, di, n), jnp.float32),
            conv=jnp.zeros((batch, cfg.conv_width - 1, di), dt),
        )
    if spec.mixer == RGLRU:
        return RGLRUState(
            h=jnp.zeros((batch, cfg.lru_width), jnp.float32),
            conv=jnp.zeros((batch, cfg.conv_width - 1, cfg.lru_width), dt),
        )
    raise ValueError(spec.mixer)


def init_decode_state(cfg: ModelConfig, batch: int, seq_budget: int,
                      pos: int = 0) -> DecodeState:
    pattern, nb, tail = cfg.scan_split()

    def one_block(_):
        return tuple(
            init_layer_state(cfg, spec, batch, seq_budget) for spec in pattern
        )

    blocks = (
        jax.vmap(one_block)(jnp.arange(nb)) if nb > 0 else None
    )
    tail_states = [
        init_layer_state(cfg, spec, batch, seq_budget) for spec in tail
    ]
    lead = tuple(init_layer_state(cfg, spec, batch, seq_budget)
                 for spec in cfg.lead_layers())
    return DecodeState(blocks=blocks, tail=tail_states,
                       pos=jnp.asarray(pos, jnp.int32), lead=lead)


def apply_layer_decode(params, state, spec, cfg: ModelConfig, x, pos, cos, sin):
    dt = _dtype(cfg)
    eps = cfg.norm_eps
    h = rmsnorm(params["norm1"], x, eps)
    if spec.mixer == MLA:
        m, new_state = mla_decode(
            params["mixer"], h, state, pos, cos, sin, rank=cfg.kv_lora_rank,
            nope=cfg.qk_nope_head_dim, dtype=dt, eps=eps,
        )
    elif spec.mixer in (ATTN, ATTN_LOCAL):
        m, new_state = attn_mod.attention_decode(
            params["mixer"], h, state, pos, cos, sin, dtype=dt, eps=eps,
            window=spec.window, softcap=cfg.attn_logit_softcap,
            use_rope=cfg.use_rope,
        )
    elif spec.mixer == MAMBA:
        m, new_state = mamba_decode(params["mixer"], h, state, dtype=dt)
    elif spec.mixer == RGLRU:
        m, new_state = rglru_decode(params["mixer"], h, state, dtype=dt)
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    x = x + m
    if spec.ffn != NONE:
        h = rmsnorm(params["norm2"], x, eps)
        f, _, _ = _ffn(params["ffn"], spec, cfg, h)
        x = x + f
    return x, new_state


def decode_step(params, cfg: ModelConfig, state: DecodeState,
                batch: Dict[str, jax.Array]) -> Tuple[jax.Array, DecodeState]:
    """One token for every sequence in the batch.

    batch: {"tokens": (B, 1)} or {"embeds": (B, 1, d)}.
    Returns (logits (B, 1, V), new state).
    """
    dt = _dtype(cfg)
    x, B, _ = _input_x(params, cfg, batch)
    pos = state.pos
    pos_ids = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    if cfg.mrope_sections:
        pos_ids = jnp.broadcast_to(pos_ids[..., None], (B, 1, 3))
    cos, sin = rope_angles(pos_ids, cfg.rope_dim, cfg.rope_theta,
                           cfg.mrope_sections)

    new_lead = []
    for i, spec in enumerate(cfg.lead_layers()):
        x, ns = apply_layer_decode(
            params["lead"][i], state.lead[i], spec, cfg, x, pos, cos, sin
        )
        new_lead.append(ns)
    pattern, nb, tail = cfg.scan_split()
    new_blocks = None
    if nb > 0:
        # The stacked caches ride in the scan CARRY and are updated in place
        # (dynamic_update_index_in_dim on the carry) — the xs->ys formulation
        # would materialize a second full cache buffer (measured +2x HBM on
        # the 32k decode cells; see EXPERIMENTS.md §Perf).
        def apply_block(h, bp, bs):
            new_states = []
            for i, spec in enumerate(pattern):
                h, ns = apply_layer_decode(
                    bp[f"l{i}"], bs[i], spec, cfg, h, pos, cos, sin
                )
                new_states.append(ns)
            return h, tuple(new_states)

        if cfg.scan_layers:
            def block_fn(carry, xs):
                h, caches = carry
                bp, bi = xs
                bs = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, bi, 0, keepdims=False), caches)
                h, ns = apply_block(h, bp, bs)
                caches = jax.tree.map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), bi, 0), caches, ns)
                return (h, caches), None

            (x, new_blocks), _ = jax.lax.scan(
                block_fn, (x, state.blocks),
                (params["blocks"], jnp.arange(nb)),
            )
        else:
            caches = state.blocks
            for bi in range(nb):
                bp = jax.tree.map(lambda p: p[bi], params["blocks"])
                bs = jax.tree.map(lambda c: c[bi], caches)
                x, ns = apply_block(x, bp, bs)
                caches = jax.tree.map(
                    lambda c, n: c.at[bi].set(n.astype(c.dtype)), caches, ns)
            new_blocks = caches
    new_tail = []
    for i, spec in enumerate(tail):
        x, ns = apply_layer_decode(
            params["tail"][i], state.tail[i], spec, cfg, x, pos, cos, sin
        )
        new_tail.append(ns)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed_logits(params["embed"], x, dt)
    else:
        logits = x @ params["lm_head"]["w"].astype(dt)
    return logits, DecodeState(blocks=new_blocks, tail=new_tail, pos=pos + 1,
                               lead=tuple(new_lead))
