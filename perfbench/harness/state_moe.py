"""Weights of a DeepSeek-V3-style configuration (Moonlight) made on the
device from the seed, by the benchmark and not by the program, laid out as
the program holds them: the leading dense layers in the list ``lead``, the
MoE layers stacked on a leading axis under ``blocks/l0``, an untied
``lm_head``. Each leaf is drawn as ``harness.state`` draws it (leaf ``i``
from ``fold_in(PRNGKey(seed), i)`` alone), but for the routers' correction
biases: these are set to spread a sequence of random tokens (also drawn
from the seed) evenly over the experts, as the bias rule holds them in a
job past its start (``harness.reference_mla_moe.balance_router_bias``)."""
from __future__ import annotations

from typing import Any, List, Tuple

from harness.state import Leaf, make_leaf


def _mixer(c: dict, base: Tuple, lead: Tuple[int, ...]) -> List[Leaf]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    m = base + ("mixer",)
    f32 = "float32"
    return [
        Leaf(base + ("norm1", "scale"), lead + (d,), f32, "normal", 0.1),
        Leaf(m + ("wq",), lead + (d, h, nope + rope), f32, "normal", d ** -0.5),
        Leaf(m + ("wkv_a",), lead + (d, rank + rope), f32, "normal", d ** -0.5),
        Leaf(m + ("kv_norm", "scale"), lead + (rank,), f32, "normal", 0.1),
        Leaf(m + ("wkv_b",), lead + (rank, h, nope + v), f32, "normal",
             rank ** -0.5),
        Leaf(m + ("wo",), lead + (h, v, d), f32, "normal", (h * v) ** -0.5),
        Leaf(base + ("norm2", "scale"), lead + (d,), f32, "normal", 0.1),
    ]


def _swiglu(base: Tuple, lead: Tuple[int, ...], d: int, ff: int,
            names=("gate", "up", "down")) -> List[Leaf]:
    g, u, o = names
    return [Leaf(base + (g,), lead + (d, ff), "float32", "normal", d ** -0.5),
            Leaf(base + (u,), lead + (d, ff), "float32", "normal", d ** -0.5),
            Leaf(base + (o,), lead + (ff, d), "float32", "normal", ff ** -0.5)]


def param_leaves(c: dict) -> List[Leaf]:
    """The parameters of configuration ``c`` (its file's keys), in f32:
    matrices fan-in scaled, the embedding at 0.02, norm scales (applied as
    ``1 + scale``) at 0.1, each router's correction bias at 0 (``maker``
    balances it)."""
    d, V = c["hidden_size"], c["vocab_size"]
    n_lead = c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - n_lead
    held, E = c["n_routed_experts"], c["router_experts"]
    f = c["moe_intermediate_size"]
    shared = c["n_shared_experts"] * f
    f32 = "float32"
    leaves = [
        Leaf(("embed", "table"), (V, d), f32, "normal", 0.02),
        Leaf(("final_norm", "scale"), (d,), f32, "normal", 0.1),
        Leaf(("lm_head", "w"), (d, V), f32, "normal", d ** -0.5),
    ]
    for i in range(n_lead):
        base = ("lead", i)
        leaves += _mixer(c, base, ())
        leaves += _swiglu(base + ("ffn",), (), d, c["intermediate_size"])
    b = ("blocks", "l0")
    ffn = b + ("ffn",)
    leaves += _mixer(c, b, (n,))
    leaves += [
        Leaf(ffn + ("router",), (n, d, E), f32, "normal", d ** -0.5),
        Leaf(ffn + ("router_bias",), (n, E), f32, "const", 0.0),
        Leaf(ffn + ("gate",), (n, held, d, f), f32, "normal", d ** -0.5),
        Leaf(ffn + ("up",), (n, held, d, f), f32, "normal", d ** -0.5),
        Leaf(ffn + ("down",), (n, held, f, d), f32, "normal", f ** -0.5),
    ]
    leaves += _swiglu(ffn, (n,), d, shared,
                      ("shared_gate", "shared_up", "shared_down"))
    return leaves


def nest(leaves: List[Leaf], values: List[Any]) -> dict:
    """The nested tree of ``values`` at the leaves' paths: integer path
    parts index the list ``lead``; ``tail`` is the program's empty list."""
    tree: dict = {"tail": []}
    for leaf, v in zip(leaves, values):
        node = tree
        for p in leaf.path[:-1]:
            node = node.setdefault(p, {})
        node[leaf.path[-1]] = v
    if "lead" in tree:
        tree["lead"] = [tree["lead"][i] for i in sorted(tree["lead"])]
    return tree


def maker(leaves: List[Leaf], c: dict, out_shardings=None):
    """A jitted ``f(key) -> tree`` that makes every leaf of configuration
    ``c`` on the device in one call, the correction biases balanced on
    ``max_position_embeddings`` tokens drawn from ``fold_in(key,
    len(leaves))``."""
    import jax

    from harness.reference_mla_moe import balance_router_bias

    balance = balance_router_bias(c)

    def make(key):
        tree = nest(leaves, [make_leaf(key, i, l) for i, l in enumerate(leaves)])
        tokens = jax.random.randint(jax.random.fold_in(key, len(leaves)),
                                    (c["max_position_embeddings"],), 0,
                                    c["vocab_size"])
        return balance(tree, tokens)

    return jax.jit(make, out_shardings=out_shardings)
