"""Persistent compile cache placement for the launchers.

Called from the command-line entry points (``repro.launch.train``,
``repro.launch.serve``, ``chip_smoke.py``), never at import or from tests.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout: a fixed path, because the path is part of the cache key
and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: repo root: src/repro/launch/compile_cache.py -> three levels up from src
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
