"""Counts the programs JAX builds, from its monitoring events.

A copy of `CompileClock` in the repository's `chip_smoke.py`: backend
compile time and count, and hits in the persistent compilation cache.
JAX reports a backend-compile duration for every program it builds,
also for one it then loads from the persistent cache (which reports a
cache hit besides), so `compiles` counts the programs built.
"""
from __future__ import annotations


class CompileClock:
    """Sums JAX's backend-compile time and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
