"""Compiles and persistent-cache loads, counted from JAX's monitoring
events, and the persistent compile cache's placement."""

from __future__ import annotations

import threading
from pathlib import Path

__all__ = ["CompileClock", "use_cache", "CACHE_DIR"]

# Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"


def use_cache() -> str:
    """Turn on JAX's persistent compile cache at :data:`CACHE_DIR` for
    every program, however short its compile, and return the path.

    The cache stays in the checkout even where
    ``$JAX_COMPILATION_CACHE_DIR`` names another directory, so that two
    checkouts measured side by side share nothing.  It is unbounded:
    one cell's programs take about 300 MB, and a bound below that (such
    as a ``$JAX_COMPILATION_CACHE_MAX_SIZE`` of 192 MiB) evicts them as
    they are written, so that no later run finds them."""
    import jax

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


class CompileClock:
    """Programs built since the last :meth:`take`: how many, how many of
    them the persistent cache returned, and the seconds of each kind.
    JAX's backend-compile event spans the cache lookup too, so it fires
    for every program, loaded or compiled."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._zero()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _zero(self):
        self.programs, self.build_s, self.loaded, self.load_s = 0, 0.0, 0, 0.0

    def _dur(self, event, secs, **_):
        with self._lock:
            if event == COMPILE:
                self.programs += 1
                self.build_s += secs
            elif event == RETRIEVE:
                self.load_s += secs

    def _event(self, event, **_):
        if event == HIT:
            with self._lock:
                self.loaded += 1

    def take(self) -> dict:
        with self._lock:
            out = {"programs": self.programs,
                   "compiled": self.programs - self.loaded,
                   "loaded": self.loaded,
                   "compile_s": self.build_s - self.load_s,
                   "load_s": self.load_s}
            self._zero()
        return out
