"""Compilations seen through JAX's monitoring events.

After ``chip_smoke.CompileStats``: backend compile seconds and
persistent-cache hits, plus the number of backend compiles.  A program
taken from the persistent cache is a hit and not a compile; both mean that
a program was built in this process.
"""
from __future__ import annotations

import jax.monitoring as mon

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileStats:
    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def programs_built(self) -> int:
        """Backend compiles plus persistent-cache hits so far."""
        return self.compiles + self.cache_hits
