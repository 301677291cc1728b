"""XLA programs built in a process, seen through ``jax.monitoring``.

``/jax/core/compile/backend_compile_duration`` is recorded around every
program JAX builds for a backend, whether it compiles it or loads it from
the persistent compilation cache; ``/jax/compilation_cache/cache_hits``
counts the loads.  Register one meter per process, before the first JAX
computation."""
from __future__ import annotations

import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.programs, self.secs, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.programs += 1
                self.secs += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def mark(self) -> tuple:
        with self._lock:
            return self.programs, self.secs, self.cache_hits

    def since(self, mark: tuple) -> dict:
        now = self.mark()
        return dict(programs=now[0] - mark[0], secs=now[1] - mark[1],
                    cache_hits=now[2] - mark[2])
