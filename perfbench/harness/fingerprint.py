"""Exact comparison of device arrays with the reference, without a second
copy of either on the host.

A leaf's fingerprint is two sums, modulo 2**32, of its 32-bit words times
odd weights that depend on each word's position: any change to one word
changes both, and a change that cancels in both is vanishingly unlikely.
The final comparison counts differing words directly."""
from __future__ import annotations


def _words(x):
    import jax
    import jax.numpy as jnp

    if x.dtype.itemsize != 4:
        raise ValueError(f"fingerprints take 32-bit leaves, not {x.dtype}")
    return jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)


def leaf_fingerprint(x):
    import jax.numpy as jnp

    w = _words(x)
    pos = jnp.arange(w.shape[0], dtype=jnp.uint32)
    w1 = pos * jnp.uint32(2654435761) | jnp.uint32(1)
    w2 = (pos ^ jnp.uint32(0x5BD1E995)) * jnp.uint32(40503) | jnp.uint32(1)
    return jnp.stack([jnp.sum(w * w1, dtype=jnp.uint32),
                      jnp.sum((w ^ jnp.uint32(0xA5A5A5A5)) * w2,
                              dtype=jnp.uint32)])


def tree_fingerprint(tree):
    """``(leaves, 2)`` uint32, in the tree's leaf order (traceable)."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([leaf_fingerprint(x) for x in jax.tree.leaves(tree)])


def differing_words(a, b):
    """Words of ``a`` whose bits differ from ``b`` (traceable)."""
    import jax.numpy as jnp

    return jnp.sum(_words(a) != _words(b), dtype=jnp.int32)
