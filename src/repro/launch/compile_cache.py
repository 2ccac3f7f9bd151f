"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache goes to a fixed
``<checkout>/.jax_cache`` — fixed because the directory is part of the
cache key, so a path that moved between runs (a temporary directory, a pid
or a timestamp in it) would never hit.  Called by the launch drivers'
``main`` and by ``chip_smoke.py`` before their first compile.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
