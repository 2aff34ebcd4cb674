"""Backend compile seconds and persistent-cache hits and misses, counted
from JAX's monitoring events (a copy of the smoke test's ``CompileLog``,
kept with the benchmark). ``compiles`` counts every backend compile and
every executable loaded from the persistent cache."""

from __future__ import annotations

import jax

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    def __init__(self) -> None:
        self.seconds = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE:
            self.seconds += secs
            self.backend_compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == HIT:
            self.hits += 1
        elif event == MISS:
            self.misses += 1

    @property
    def compiles(self) -> int:
        return self.backend_compiles + self.hits
