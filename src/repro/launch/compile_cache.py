"""JAX persistent compilation cache location for every entry point.

A cold TPU process compiles every program it runs; the persistent cache
lets the next process on the same checkout skip that. The cache key
includes the directory, so the directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: fixed for a checkout, listed in .gitignore
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is changed here. Call before the first
    compile."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
