"""Backend compile seconds and count, and persistent-cache hits, read off
``jax.monitoring``; each event is stamped so a window can count its own."""
from __future__ import annotations

import threading
import time


class CompileMeter:
    """Counts ``/jax/core/compile/backend_compile_duration`` events and
    ``/jax/compilation_cache/cache_hits``. JAX records the first around every
    program it obtains, from the compiler or from the persistent cache, so
    compiles less hits is what the compiler really built."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.stamps: list[float] = []  # perf_counter at the end of each compile
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1
                self.stamps.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def reading(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits

    def compiles_between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for t in self.stamps if t0 <= t < t1)
