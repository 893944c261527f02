"""JAX's persistent compilation cache, placed from outside the program.

The entry points call :func:`enable` before their first compile; importing
this module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and this module sets no directory.  Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout — a fixed path, because
the path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
