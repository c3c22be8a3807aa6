"""Count what JAX compiles and what its persistent cache answers,
through ``jax.monitoring`` — the program is not asked."""
import time

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts from the moment it is made. ``backend_compiles`` counts
    every program handed to the backend compiler OR read from the
    persistent cache: JAX reports both under one event."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            self.backend_compiles += 1
            self.backend_compile_s += duration

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1
        elif event == _CACHE_MISS:
            self.cache_misses += 1

    def snapshot(self):
        return {"backend_compiles": self.backend_compiles,
                "backend_compile_s": self.backend_compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Phases:
    """Wall seconds of the set-up's phases, for the earlier lines."""

    def __init__(self, start):
        self.last, self.rows = start, {}

    def mark(self, name):
        now = time.perf_counter()
        self.rows[name] = round(now - self.last, 3)
        self.last = now
