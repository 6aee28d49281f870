"""JAX persistent compilation cache for the entry points.

The launchers, the benchmark runner and ``chip_smoke.py`` call
:func:`enable` once at start-up (never at import), so repeated runs in one
checkout reuse compiled programs.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins and no other directory is configured (JAX reads the variable
itself).  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the path is part of the cache key, so a temporary or
per-process directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
