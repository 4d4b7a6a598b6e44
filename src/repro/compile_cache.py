"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable` once before their first compile; importing the library
never touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it and nothing is set here.  Otherwise the cache is kept in
``<checkout>/.jax_cache``: a fixed path, so a later run finds what an
earlier one wrote (nothing in the path comes from a temporary name, a pid
or the time).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
