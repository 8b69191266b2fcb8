"""Persistent JAX compilation cache for the entry points.

A chip run compiles every kernel and bucket executor cold unless the
compiled programs persist.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is
set (JAX reads it itself, so nothing is set here); otherwise the cache
lives at a fixed path inside the checkout, ``<repo>/.jax_cache`` — fixed
because the path is part of the cache key, so a directory that moved
between runs would never hit.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR, "").strip()
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
