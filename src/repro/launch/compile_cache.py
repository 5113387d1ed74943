"""JAX's persistent compilation cache, placed in one spot for every entry point.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set. Otherwise the cache lives
at ``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def compile_cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory. Call from an entry point, before the first
    compile."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
