"""Counts XLA compiles, their seconds and persistent-cache loads.

JAX reports one ``backend_compile_duration`` event per program it compiles
*or* loads from the persistent cache, and a ``cache_hits`` event per load;
a compile is an event that was not a load.
"""
from __future__ import annotations


class Compiles:
    def __init__(self):
        from jax import monitoring
        self.n = self.hits = 0
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self) -> tuple[int, int, float]:
        return self.n, self.hits, self.secs

    def since(self, snap) -> dict:
        n, hits, secs = snap
        loads = self.hits - hits
        return {"compiles": self.n - n - loads, "cache_loads": loads,
                "compile_s": self.secs - secs}
