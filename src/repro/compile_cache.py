"""JAX's persistent compilation cache for the repo's entry points.

Scripts (`chip_smoke.py`, `benchmarks/run.py`, `examples/*`) call
:func:`enable` before their first compile; importing the package sets
nothing, so library users and the tests keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of what a later process must
find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
