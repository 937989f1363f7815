"""JAX's persistent compilation cache, kept at a fixed place.

The cache key includes the directory, so a cache that moves never hits.
Entry points call ``use_compile_cache()`` once at start; importing this
module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself.  Otherwise the cache lives in ``.jax_cache`` at the root of
    this checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
