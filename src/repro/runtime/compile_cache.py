"""JAX's persistent compilation cache at one fixed place.

A cold process recompiles every step executable and kernel; the cache
lets the next process on the same machine load them instead.  The cache
key includes the directory, so the directory must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself), else ``.jax_cache`` at the root of this checkout.
Entry points call ``enable_compile_cache`` once, before their first
compile; tests do not, so a test run never writes to the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the cache lives: the environment's directory, else the
    checkout's ``.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
