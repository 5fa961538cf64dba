"""Counts what JAX compiles or fetches from its persistent cache.

`/jax/core/compile/backend_compile_duration` fires once for every new
executable a process needs, whether XLA compiles it or the persistent
cache serves it; either way a new shape reached the program. The count
over the measured window is `window_compiles` and has to be 0.
"""
from __future__ import annotations

import dataclasses

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class CompileCounts:
    executables: int = 0        # new executables (compiled or from cache)
    executable_s: float = 0.0   # seconds spent getting them
    cache_hits: int = 0         # ... served by the persistent cache
    cache_misses: int = 0       # ... compiled and written to it

    def copy(self) -> "CompileCounts":
        return dataclasses.replace(self)


def install() -> CompileCounts:
    """Register the listeners (for the life of the process) and return the
    live counts."""
    import jax.monitoring as monitoring
    counts = CompileCounts()

    def on_duration(event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            counts.executables += 1
            counts.executable_s += seconds

    def on_event(event: str, **_) -> None:
        if event == _CACHE_HIT:
            counts.cache_hits += 1
        elif event == _CACHE_MISS:
            counts.cache_misses += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return counts
