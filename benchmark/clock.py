"""Compile accounting from JAX's own events (copied from chip_smoke.py's
``Clock``): seconds of backend compile — the XLA compile, or its load from
the persistent cache — and the cache's hits and misses. The harness reads
it at the window's edges to show that nothing compiled inside."""

from __future__ import annotations

import threading

import jax


class CompileClock:
    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs
                self.compiles += 1

    def _event(self, event: str, **_) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}
