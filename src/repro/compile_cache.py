"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks.run``) call :func:`enable` once, before their first
compile. Tests never do: they compile small shapes, and a shared cache
directory would couple test processes.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
:func:`enable` sets nothing. Otherwise the cache goes to ``.jax_cache``
at the repository root. The path is part of every cache key, so it is
fixed: never a temporary, per-process or per-run directory, which would
never hit.

:class:`CompileLog` counts what the process compiled and what the cache
served, from JAX's own monitoring events.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileLog:
    """Backend compiles (a persistent-cache hit counts as one, at its
    retrieval time), their seconds, and the cache's hits and misses
    while the ``with`` block runs."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def __enter__(self) -> "CompileLog":
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def summary(self) -> str:
        return (f"{self.compiles} compiles, {self.seconds:.1f} s; "
                f"persistent cache {self.hits} hits, {self.misses} misses")
