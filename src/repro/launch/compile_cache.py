"""Persistent XLA compilation cache.

Call :func:`enable_compile_cache` once, before the first compile.  If
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nowhere
else; otherwise it lives at a fixed path inside the checkout
(``.jax_cache/``, git-ignored).  The directory is part of what a later
run must find again, so it is never derived from a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """Where the cache lives: the environment variable, else DEFAULT_DIR."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    return that path."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
