"""JAX's persistent compilation cache, placed for the repo's entry points.

The entry point ``chip_smoke.py`` calls :func:`enable_compile_cache`
once, before its first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: a path that moved between runs would never
    be found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT_DIR, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
