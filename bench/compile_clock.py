"""Compile accounting from ``jax.monitoring`` (copied from the smoke run's
``CompileClock``, so a change to the program cannot change how the
benchmark counts).

Every executable JAX builds or loads records one backend-compile event
(``/jax/core/compile/backend_compile_duration``); one loaded from the
persistent cache also records a cache retrieval.  So ``programs`` counts
executables that a call needed and did not have, ``cache_hits`` the part
of them read from disk, and ``programs - cache_hits`` true XLA compiles.
"""
from __future__ import annotations

import dataclasses
import threading

BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass(frozen=True)
class CompileCounts:
    programs: int = 0
    cache_hits: int = 0
    compile_s: float = 0.0        # tracing, lowering and compiling
    backend_s: float = 0.0        # the backend-compile part of it

    def __sub__(self, other: "CompileCounts") -> "CompileCounts":
        return CompileCounts(self.programs - other.programs,
                             self.cache_hits - other.cache_hits,
                             self.compile_s - other.compile_s,
                             self.backend_s - other.backend_s)

    def line(self) -> str:
        return (f"programs={self.programs} cache_hits={self.cache_hits} "
                f"compiled={self.programs - self.cache_hits} "
                f"compile_s={self.compile_s:.3f} "
                f"backend_s={self.backend_s:.3f}")


class CompileClock:
    """Running totals of compile events; ``snapshot`` reads them."""

    def __init__(self, monitoring):
        self._lock = threading.Lock()
        self._now = CompileCounts()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        with self._lock:
            c = self._now
            if name.startswith("/jax/core/compile/"):
                backend = name == BACKEND_EVENT
                self._now = CompileCounts(
                    c.programs + backend, c.cache_hits,
                    c.compile_s + secs, c.backend_s + secs * backend)
            elif name == RETRIEVAL_EVENT:
                self._now = CompileCounts(c.programs, c.cache_hits + 1,
                                          c.compile_s, c.backend_s)

    def snapshot(self) -> CompileCounts:
        with self._lock:
            return self._now
