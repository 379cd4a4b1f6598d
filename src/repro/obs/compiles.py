"""Per-thread compile accounting from ``jax.monitoring``.

JAX traces, lowers and compiles a program on the thread that first calls
it, and reports each step as a ``/jax/core/compile/*`` duration event on
that thread.  One process-wide listener adds those seconds, and one count
per backend compile, to the calling thread's running totals.  A span
reads the totals when it opens and when it closes: the difference is the
compiling done inside it (``Tracer.span``'s ``compile_s`` / ``compiles``
attributes; a ``launch`` span's make the per-program record).

A program loaded from JAX's persistent compilation cache still reports a
backend compile (the load), so ``compiles`` counts executables a call
needed and did not have in memory, as the benchmark's compile clock does.
"""
from __future__ import annotations

import threading

COMPILE_PREFIX = "/jax/core/compile/"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()
_install_lock = threading.Lock()
_installed = False


def _on_event(name: str, secs: float, **_) -> None:
    if not name.startswith(COMPILE_PREFIX):
        return
    _local.seconds = getattr(_local, "seconds", 0.0) + secs
    if name == BACKEND_EVENT:
        _local.count = getattr(_local, "count", 0) + 1


def install() -> None:
    """Register the listener, once per process (idempotent)."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _installed = True


def thread_compiles() -> tuple[float, int]:
    """(compile seconds, backend compiles) on the calling thread so far."""
    return getattr(_local, "seconds", 0.0), getattr(_local, "count", 0)
