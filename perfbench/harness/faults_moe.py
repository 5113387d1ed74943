"""Faults planted in the dropless MoE layer, for the checks of the
Moonlight cell that must catch them: each wraps a function of the program's
``repro.models.moe`` and breaks what it produces."""
from __future__ import annotations


def drop_above_capacity(held_expert_rows, experts: int,
                        capacity_factor: float = 1.0):
    """Dispatch that drops tokens: each held expert keeps at most
    ``capacity_factor x tokens x k / experts`` of the rows routed to it,
    and the rest give nothing."""
    def rows(idx, first, held):
        import jax.numpy as jnp

        tok, pair, valid, sizes = held_expert_rows(idx, first, held)
        T, k = idx.shape
        cap = max(1, int(capacity_factor * T * k / experts))
        ends = jnp.cumsum(sizes)
        row = jnp.arange(valid.shape[0])
        group = jnp.minimum(jnp.searchsorted(ends, row, side="right"),
                            held - 1)
        rank = row - (ends - sizes)[group]
        return tok, pair, valid & (rank < cap), sizes

    return rows


def bias_left_out(sigmoid_route):
    """Routing that chooses on the scores alone, without the correction
    bias."""
    def route(h, router, bias, top_k, scaling):
        import jax.numpy as jnp

        return sigmoid_route(h, router, jnp.zeros_like(bias), top_k, scaling)

    return route
