"""Counts of compilation inside a window, from JAX's monitoring events.

``CompileClock`` is a copy of the one in ``chip_smoke.py``: seconds and
count of backend compiles. It also counts persistent-cache retrievals, the
other way a program that was not warmed up reaches its first run.
"""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Backend compiles and cache retrievals since the last ``take``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, fun_name: str = "?", **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1
            self.names.append(fun_name)

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def take(self) -> tuple[float, int, int, list]:
        """Seconds and count of compiles, cache hits, and the compiled
        programs' names, since the last call."""
        out = (self.seconds, self.compiles, self.cache_hits, self.names)
        self.seconds, self.compiles, self.cache_hits, self.names = 0.0, 0, 0, []
        return out
