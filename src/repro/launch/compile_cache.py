"""JAX's persistent compilation cache for the entry points.

A cache is found again only at the same path, so the path is fixed: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the
variable itself), otherwise ``.jax_cache/`` at the root of the checkout.
Entry points call :func:`enable` from ``main``; importing ``repro`` never
touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
