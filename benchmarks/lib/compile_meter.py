"""Seconds JAX spent producing executables, and how the persistent cache
answered, from JAX's own monitoring events (a cache hit counts the time
its retrieval took).  The pattern is chip_smoke.py's `_CompileMeter`."""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event.endswith("backend_compile_duration"):
            self.seconds += float(duration)
            self.count += 1

    def _on_event(self, event, **_kw):
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.hits, "cache_misses": self.misses}
