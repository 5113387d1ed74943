"""Mixture-of-Experts FFN: top-k routing, capacity-based sort+gather dispatch,
shared experts (Qwen-MoE style), Switch-style load-balancing auxiliary loss.

Dispatch strategy (TPU/SPMD-native, flop-sane):
  routing is done *per sequence group* (the batch row), so no cross-shard
  sort is required; token slots are assigned with an argsort over S·k
  elements per row; expert inputs are built by gather into an (E, C, d)
  capacity buffer; expert FFNs run as batched einsums with the expert axis
  sharded over `model` (expert parallelism). XLA inserts the dispatch/combine
  gathers as the EP collectives. Dominant FLOPs = capacity_factor × ideal
  active FLOPs (vs the T² blow-up of naive one-hot dispatch einsums — see
  EXPERIMENTS.md §Perf for the measured comparison).

``moe_apply_dropless`` is DeepSeek-V3's layer (arXiv:2412.19437 §2.1.2) as
one expert-parallel share: sigmoid scores over every expert, top-k chosen
on the scores plus a correction bias that has no gradient, the chosen
scores normalised and scaled; the layer holds experts
``[first, first + held)`` and computes their part of the result for every
row routed to them (no capacity, no dropped token) with a grouped matmul
whose work follows the rows routed, plus the shared experts.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import MODEL_AXIS, fan_in_init, shard_act


def moe_init(key, d: int, num_experts: int, moe_ff: int, num_shared: int,
             dtype, expert_pad: int = 0, held: int = 0,
             router_bias: bool = False) -> dict:
    """``held`` experts' weights (0: all of them) beside a router over all
    ``num_experts + expert_pad``; ``router_bias`` adds the correction bias
    of sigmoid routing (zero)."""
    ks = jax.random.split(key, 5)
    ep = num_experts + expert_pad    # physical experts (EP divisibility)
    n = held or ep
    p = {
        "router": fan_in_init(ks[0], (d, ep), d, dtype),
        "gate": fan_in_init(ks[1], (n, d, moe_ff), d, dtype),
        "up": fan_in_init(ks[2], (n, d, moe_ff), d, dtype),
        "down": fan_in_init(ks[3], (n, moe_ff, d), moe_ff, dtype),
    }
    if router_bias:
        p["router_bias"] = jnp.zeros((ep,), dtype=dtype)
    if num_shared > 0:
        ff_sh = num_shared * moe_ff
        kg, ku, kd = jax.random.split(ks[4], 3)
        p["shared_gate"] = fan_in_init(kg, (d, ff_sh), d, dtype)
        p["shared_up"] = fan_in_init(ku, (d, ff_sh), d, dtype)
        p["shared_down"] = fan_in_init(kd, (ff_sh, d), ff_sh, dtype)
    return p


def _route(
    logits: jax.Array,       # (B, S, E) fp32
    top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-row slot assignment.

    Returns:
      idx_table (B, E*C) int32 — token index feeding each expert slot
                                 (S = sentinel → zero row),
      slot_of   (B, S, k) int32 — expert slot per (token, choice),
                                  E*C = sentinel (dropped),
      weight    (B, S, k) fp32  — router weight per choice,
      probs     (B, S, E) fp32  — full router probabilities (for aux loss).
    """
    B, S, E = logits.shape
    k = top_k
    C = capacity
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # (B,S,k)

    eid = top_e.reshape(B, S * k)
    # stable sort by expert id so earlier tokens win capacity (Switch rule)
    order = jnp.argsort(eid, axis=-1, stable=True)          # (B, S*k)
    eid_sorted = jnp.take_along_axis(eid, order, axis=-1)
    tok_sorted = order // k                                  # token of each entry

    # position within the expert segment
    seg_start = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E), side="left")
    )(eid_sorted)                                            # (B, E)
    start_of = jnp.take_along_axis(seg_start, eid_sorted, axis=-1)
    pos = jnp.arange(S * k)[None, :] - start_of              # (B, S*k)
    keep = pos < C

    dest = eid_sorted * C + pos                              # (B, S*k)
    dest_safe = jnp.where(keep, dest, E * C)                 # sentinel slot

    # expert-slot -> token table (scatter; sentinel token index = S)
    def scatter_row(tok_row, dest_row):
        t = jnp.full((E * C + 1,), S, dtype=jnp.int32)
        return t.at[dest_row].set(tok_row.astype(jnp.int32))[: E * C]

    idx_table = jax.vmap(scatter_row)(tok_sorted, dest_safe)  # (B, E*C)

    # token -> slot back-map (unsort)
    def unsort_row(dest_row, order_row):
        out = jnp.zeros((S * k,), dtype=jnp.int32)
        return out.at[order_row].set(dest_row.astype(jnp.int32))

    slot_of = jax.vmap(unsort_row)(dest_safe, order).reshape(B, S, k)
    return idx_table, slot_of, top_w, probs


def load_balance_loss(probs: jax.Array, slot_of: jax.Array, num_experts: int,
                      top_k: int, capacity: int) -> jax.Array:
    """Switch-Transformer aux loss: E * sum_e f_e * P_e."""
    B, S, E = probs.shape
    served = slot_of < E * capacity                          # (B,S,k) kept
    expert_of_slot = jnp.clip(slot_of // capacity, 0, E - 1)
    onehot = jax.nn.one_hot(expert_of_slot, E, dtype=jnp.float32) * served[
        ..., None
    ].astype(jnp.float32)
    f = onehot.sum(axis=(1, 2)) / jnp.maximum(S * top_k, 1)  # (B,E) token fraction
    p = probs.mean(axis=1)                                   # (B,E) prob fraction
    return jnp.mean(jnp.sum(f * p, axis=-1)) * E


def moe_apply(
    params: dict,
    x: jax.Array,               # (B, S, d)
    *,
    top_k: int,
    capacity_factor: float,
    dtype,
    norm_topk: bool = False,
    num_real_experts: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,d), aux_loss scalar fp32)."""
    B, S, d = x.shape
    E = params["router"].shape[1]      # physical (possibly padded) experts
    n_real = num_real_experts or E
    C = max(1, int(capacity_factor * top_k * S / max(n_real, 1) + 0.5))

    router_logits = (x.astype(jnp.float32)
                     @ params["router"].astype(jnp.float32))  # (B,S,E)
    if n_real < E:   # mask padded experts out of routing
        pad_mask = jnp.arange(E) >= n_real
        router_logits = jnp.where(pad_mask[None, None], -1e30, router_logits)
    idx_table, slot_of, top_w, probs = _route(router_logits, top_k, C)
    aux = load_balance_loss(probs, slot_of, E, top_k, C)

    if norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # dispatch: gather expert inputs (sentinel row S -> zeros)
    xp = jnp.concatenate([x, jnp.zeros((B, 1, d), dtype=x.dtype)], axis=1)
    xe = jnp.take_along_axis(xp, idx_table[..., None], axis=1)  # (B, E*C, d)
    xe = xe.reshape(B, E, C, d)
    xe = shard_act(xe, "batch", MODEL_AXIS, None, None)

    g = jnp.einsum("becd,edf->becf", xe, params["gate"].astype(dtype))
    u = jnp.einsum("becd,edf->becf", xe, params["up"].astype(dtype))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, params["down"].astype(dtype))
    ye = shard_act(ye, "batch", MODEL_AXIS, None, None)

    # combine: gather each token's k slot outputs, weighted sum
    ye_flat = ye.reshape(B, E * C, d)
    yp = jnp.concatenate([ye_flat, jnp.zeros((B, 1, d), dtype=ye.dtype)], axis=1)
    slot_safe = jnp.minimum(slot_of, E * C)                  # sentinel -> zeros
    picked = jnp.take_along_axis(
        yp, slot_safe.reshape(B, S * top_k)[..., None], axis=1
    ).reshape(B, S, top_k, d)
    out = jnp.sum(picked * top_w[..., None].astype(picked.dtype), axis=2)

    # shared experts (always-on dense path, Qwen-MoE style)
    if "shared_gate" in params:
        out = out + shared_experts(params, x, dtype)

    return out.astype(x.dtype), aux


def shared_experts(params: dict, x: jax.Array, dtype) -> jax.Array:
    sg = x @ params["shared_gate"].astype(dtype)
    su = x @ params["shared_up"].astype(dtype)
    sh = jax.nn.silu(sg) * su
    sh = shard_act(sh, "batch", None, MODEL_AXIS)
    return sh @ params["shared_down"].astype(dtype)


def sigmoid_route(h: jax.Array, router: jax.Array, bias: jax.Array,
                  top_k: int, scaling: float
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeek-V3 noaux_tc routing with one group, in float32.

    h: (T, d). Returns (chosen experts (T, k) int32, their weights (T, k):
    the chosen scores over their sum times ``scaling``, scores (T, E))."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
    return idx, weight, scores


def sequence_balance_loss(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss before its weight alpha:
    per sequence sum_i f_i P_i, with f_i = E / (k S) x (tokens choosing i)
    and P_i the mean over the sequence of i's score over the token's score
    sum; the mean over sequences. scores (B, S, E), idx (B, S, k)."""
    B, S, E = scores.shape
    k = idx.shape[-1]
    chosen = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], idx.reshape(B, S * k)].add(1.0)
    f = chosen * (E / (k * S))
    p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def held_expert_rows(idx: jax.Array, first: int, held: int):
    """Dispatch plan of the held experts: the (token, choice) pairs routed to
    experts ``[first, first + held)``, sorted by expert, in a buffer of the
    most such pairs there can be (T x min(k, held)).

    Returns (token of each buffer row (M,), the flat (token, choice) index
    of each row (M,), which rows hold a routed pair (M,) bool, rows per held
    expert (held,) int32)."""
    T, k = idx.shape
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    M = T * min(k, held)
    pair = order[:M].astype(jnp.int32)
    valid = jnp.arange(M) < jnp.sum(sizes)
    return pair // k, pair, valid, sizes


def expert_ffn(xs: jax.Array, sizes: jax.Array, params: dict, dtype
               ) -> jax.Array:
    """SwiGLU of each held expert on its rows of ``xs`` (sorted by expert,
    ``sizes`` rows each): a grouped matmul whose work follows ``sizes``."""
    g = jax.lax.ragged_dot(xs, params["gate"].astype(dtype), sizes)
    u = jax.lax.ragged_dot(xs, params["up"].astype(dtype), sizes)
    return jax.lax.ragged_dot(jax.nn.silu(g) * u,
                              params["down"].astype(dtype), sizes)


def moe_apply_dropless(
    params: dict,
    x: jax.Array,               # (B, S, d)
    *,
    top_k: int,
    scaling: float,
    first: int,
    dtype,
) -> Tuple[jax.Array, jax.Array, dict]:
    """Returns (output (B, S, d), balance loss, stats): ``stats`` holds each
    expert's load (tokens that chose it, every expert, float32), the rows
    the held experts computed and the rows of the busiest held expert."""
    B, S, d = x.shape
    held = params["gate"].shape[0]
    with jax.named_scope("moe.route"):
        idx, weight, scores = sigmoid_route(
            x.reshape(B * S, d), params["router"], params["router_bias"],
            top_k, scaling)
        E = scores.shape[-1]
        aux = sequence_balance_loss(scores.reshape(B, S, E),
                                    idx.reshape(B, S, top_k))
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        tok, pair, valid, sizes = held_expert_rows(idx, first, held)
    with jax.named_scope("moe.experts"):
        xf = x.reshape(B * S, d)
        # Rows past the routed ones are outside every group: zero them, and
        # their results, so that nothing the grouped matmul leaves there
        # reaches the output or the gradient.
        xs = jnp.where(valid[:, None], xf[tok], 0).astype(dtype)
        ys = jnp.where(valid[:, None], expert_ffn(xs, sizes, params, dtype), 0)
    with jax.named_scope("moe.combine"):
        w = jnp.where(valid, weight.reshape(-1)[pair], 0.0)
        out = jnp.zeros((B * S, d), ys.dtype).at[tok].add(
            ys * w[:, None].astype(ys.dtype))
        out = out.reshape(B, S, d)
        if "shared_gate" in params:
            out = out + shared_experts(params, x, dtype)
    stats = {"load": load, "rows": jnp.sum(sizes), "rows_max": jnp.max(sizes)}
    return out.astype(x.dtype), aux, stats
