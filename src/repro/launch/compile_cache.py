"""JAX persistent compilation cache for the entry points.

Called from ``main`` of each entry point (``repro.launch.serve`` and
``chip_smoke.py``), never at import. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it. Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout: a fixed path,
because the directory is part of every cache key and a path made from a
temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout fallback (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
