"""JAX's persistent compilation cache for this checkout's programs.

Entry points call :func:`enable_compile_cache` first thing in ``main``;
nothing calls it at import.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing.  Otherwise the cache lives at
one fixed path inside the checkout (``.jax_cache``, gitignored), which
every later process of the checkout finds again; a path built from a
temp name, a pid or the time would start empty every run.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
