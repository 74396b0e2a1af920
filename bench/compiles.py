"""The harness's own compile counter: a ``jax.monitoring`` listener that
counts backend compiles and persistent-cache loads while it is armed."""
from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """``arm()`` starts a count and ``disarm()`` ends it and returns it:
    ``compiles`` (the backend ran) plus ``cache_loads`` (a program came
    from the persistent cache instead).  Each instance registers its own
    listeners, which stay registered for the life of the process."""

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0
        self._armed = False
        self._lock = threading.Lock()
        import jax.monitoring as jm
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE and self._armed:
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def _on_event(self, event, **kw):
        if event == CACHE_HIT and self._armed:
            with self._lock:
                self.cache_loads += 1

    @property
    def total(self) -> int:
        return self.compiles + self.cache_loads

    def arm(self):
        self.compiles = self.cache_loads = 0
        self.compile_s = 0.0
        self._armed = True

    def disarm(self) -> int:
        self._armed = False
        return self.total
