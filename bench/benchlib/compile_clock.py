"""Seconds JAX spends getting its programs ready in set-up, read from
``jax.monitoring``: the backend-compile span covers compiling and reading a
compiled program back from the persistent cache alike; cache hits are
counted apart, so ``count - hits`` programs were really compiled."""

from __future__ import annotations

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, duration, **_):
        if event == COMPILE:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == HIT:
            self.hits += 1
