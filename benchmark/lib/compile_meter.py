"""What JAX itself reports about compilation in this process (copied in
idea from chip_smoke.CompileMeter): programs handed to the backend
compiler, seconds in trace+lower+compile, persistent-cache hits. The
window's own count of compilations comes from here, not from the
program's (kind, B, k) table, which misses the encoder and the widening
search."""

from __future__ import annotations

import threading
from typing import Dict

_DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in _DURATIONS:
            with self._lock:
                self.seconds += duration
                if event == _DURATIONS[-1]:
                    self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compile_s": self.seconds, "programs": self.programs,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}
