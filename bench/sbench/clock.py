"""Counts XLA compiles and their time from JAX's monitoring events."""
from __future__ import annotations

import jax


class CompileClock:
    """Sums backend compile time (persistent-cache retrievals included)
    and counts compiles and persistent-cache hits."""

    def __init__(self):
        self.secs, self.n, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return {"compile_s": self.secs, "compiles": self.n,
                "cache_hits": self.hits}
