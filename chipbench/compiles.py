"""Seconds spent in XLA compilation and persistent-cache hits and misses,
from ``jax.monitoring`` (a hit counts its retrieval time).  A copy of
``chip_smoke.py``'s ``_Compiles``: the yardstick does not import it."""

from __future__ import annotations


class Compiles:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "count": self.count,
                "hits": self.hits, "misses": self.misses}
