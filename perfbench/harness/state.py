"""Weights and train state made on the device from the seed, by the
benchmark and not by the program, so that the plain reference can make the
same values again without taking anything the program made.

Leaves are laid out as the program's dense transformer holds them (the
layers stacked on a leading axis under ``blocks/l0``). Leaf ``i`` of a tree
is drawn from ``fold_in(PRNGKey(seed), i)`` alone, so that any one leaf can
be made again by itself."""
from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple


class Leaf(NamedTuple):
    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: str
    kind: str          # "normal" (times scale), "square" (of a normal times scale), "const"
    scale: float


def param_leaves(c: dict, prefix: Tuple[str, ...] = ()) -> List[Leaf]:
    """The parameters of the configuration ``c`` (its file's keys), in f32:
    matrices fan-in scaled, the embedding at 0.02, norm scales (applied as
    ``1 + scale``) at 0.1."""
    d, hd, ff = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, k, n = (c["num_attention_heads"], c["num_key_value_heads"],
               c["num_hidden_layers"])
    b = prefix + ("blocks", "l0")
    f32 = "float32"
    return [
        Leaf(prefix + ("embed", "table"), (c["vocab_size"], d), f32, "normal", 0.02),
        Leaf(prefix + ("final_norm", "scale"), (d,), f32, "normal", 0.1),
        Leaf(b + ("norm1", "scale"), (n, d), f32, "normal", 0.1),
        Leaf(b + ("mixer", "wq"), (n, d, h, hd), f32, "normal", d ** -0.5),
        Leaf(b + ("mixer", "wk"), (n, d, k, hd), f32, "normal", d ** -0.5),
        Leaf(b + ("mixer", "wv"), (n, d, k, hd), f32, "normal", d ** -0.5),
        Leaf(b + ("mixer", "wo"), (n, h, hd, d), f32, "normal", (h * hd) ** -0.5),
        Leaf(b + ("norm2", "scale"), (n, d), f32, "normal", 0.1),
        Leaf(b + ("ffn", "gate"), (n, d, ff), f32, "normal", d ** -0.5),
        Leaf(b + ("ffn", "up"), (n, d, ff), f32, "normal", d ** -0.5),
        Leaf(b + ("ffn", "down"), (n, ff, d), f32, "normal", ff ** -0.5),
    ]


def train_state_leaves(c: dict, step: int) -> List[Leaf]:
    """A train state as a training job checkpoints it: f32 parameters and the
    two Adam moments (first moment ~1e-4, second its square), and the step."""
    params = param_leaves(c, ("params",))
    mu = [l._replace(path=("opt", "mu") + l.path[1:], kind="normal",
                     scale=1e-4) for l in params]
    nu = [l._replace(path=("opt", "nu") + l.path[1:], kind="square",
                     scale=1e-4) for l in params]
    return params + mu + nu + [Leaf(("opt", "step"), (), "int32", "const",
                                    float(step))]


def make_leaf(key, i: int, leaf: Leaf):
    """Leaf ``i`` of a tree whose base key is ``key`` (traceable)."""
    import jax
    import jax.numpy as jnp

    if leaf.kind == "const":
        return jnp.full(leaf.shape, leaf.scale, jnp.dtype(leaf.dtype))
    x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)
    x = x * leaf.scale
    if leaf.kind == "square":
        x = x * x
    return x.astype(jnp.dtype(leaf.dtype))


def nest(leaves: List[Leaf], values: List[Any]) -> dict:
    """The nested dict of ``values`` at the leaves' paths, with the program's
    empty ``tail`` list beside each ``blocks``."""
    tree: dict = {}
    for leaf, v in zip(leaves, values):
        node = tree
        for p in leaf.path[:-1]:
            node = node.setdefault(p, {})
        node[leaf.path[-1]] = v
        if "blocks" in leaf.path:
            parent = tree
            for p in leaf.path[:leaf.path.index("blocks")]:
                parent = parent[p]
            parent.setdefault("tail", [])
    return tree


def maker(leaves: List[Leaf], out_shardings=None):
    """A jitted ``f(key) -> tree`` that makes every leaf on the device in
    one call."""
    import jax

    def make(key):
        return nest(leaves, [make_leaf(key, i, l) for i, l in enumerate(leaves)])

    return jax.jit(make, out_shardings=out_shardings)


def base_key(seed: int):
    import jax

    return jax.random.PRNGKey(seed)
