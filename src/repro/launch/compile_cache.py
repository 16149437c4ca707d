"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py`` and the ``launch/``
mains): if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set in code; otherwise the cache lives at one fixed directory
inside the checkout (``<repo>/.jax_cache``, gitignored). The path is part
of what makes a cache entry findable again, so it never depends on a temp
dir, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "init_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
