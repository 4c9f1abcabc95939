"""Persistent XLA compile cache for the command-line entry points.

A cold run at the paper's sizes spends minutes compiling (the service's
prepare and chunk programs plus one Pallas kernel per hierarchy level),
so every entry point keeps its compiled programs across processes.
Call :func:`use_compile_cache` from ``main()``, never at import: tests
and deviceless compiles must not write to a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "CACHE_DIR"]

# Fixed, inside the checkout (gitignored): the path is part of the cache
# key, so a directory that moves between runs never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache lives at :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
