"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`enable_compile_cache` before it compiles.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
sets nothing. Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout: a fixed path, because the path is part of the cache's key, so a
directory that moved between runs (a temp name, a pid, a time) never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
