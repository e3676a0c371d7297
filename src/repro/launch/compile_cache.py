"""JAX's persistent compilation cache, switched on by entry points.

Compiling each served shape is a large part of a cold run (one search
program at 4 x 262k x 128 compiles in about 15 s), and a cache only hits
when its directory stays put: the path is part of the key. So the cache
lives either where `JAX_COMPILATION_CACHE_DIR` says, which JAX reads
itself, or at one fixed directory inside the checkout. Entry points call
`enable_compile_cache()`; importing a library module never does.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
