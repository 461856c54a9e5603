"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points that compile for the chip call :func:`enable_compile_cache`
before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it at start-up and the cache goes there; nothing here sets another
directory.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what a later run must
find again.  :class:`CompileClock` sums the seconds a process spends in
backend compiles, so a second run shows the cache hits as a lower total.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout default (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Every compile is cached, however short: a bring-up run compiles many
    small kernels, and each one is a chip-second on the next run.
    """
    path = os.environ.get(CACHE_VAR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Running totals of this process's backend compiles.

    ``seconds`` sums JAX's backend-compile durations (a persistent-cache hit
    counts only its retrieval); ``cache_hits`` counts the compiles the
    persistent cache answered.  Listeners cannot be removed, so make one
    clock per process.
    """

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self._COMPILE:
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.cache_hits += 1
