"""Where compiled programs persist between processes, and how long a
process spends compiling.

Launchers call these from ``main``, never at import: the tests import the
library and must not move any cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the span JAX times around every backend compile or persistent-cache read
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    nothing is changed here. Otherwise the cache is ``<checkout>/.jax_cache``,
    resolved from this package's path — a fixed directory, so every process
    of this checkout (record, resume, replay workers) finds what the others
    compiled."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Sums, from construction on, the seconds this process spends in
    backend compiles (a persistent-cache hit counts its read time)."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def __str__(self) -> str:
        return f"xla compile {self.seconds:.3f}s over {self.programs} programs"
