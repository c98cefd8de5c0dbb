"""Persistent compilation cache for the entry points, and the engine's count
of the XLA programs it builds.

``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/*.py`` call
:func:`enable` before their first JAX computation; importing ``repro`` never
does, so the tests write no cache.

:func:`listen` registers the one ``jax.monitoring`` listener of the process
(every ``Executor`` calls it): a process-wide count of programs built and of
persistent-cache hits (:func:`counts`), and, for the statement whose plan
node is compiling on that thread, ``ExecStats.compiles`` / ``compile_ns``
and — traced — a ``compile`` span backdated to when the build began.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

# a fixed path: the cache is only found again if every run uses the same one
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# fired once per program built: compiled, or loaded from the persistent cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_LOCK = threading.Lock()
_listening = False
_programs = 0
_hits = 0


def enable() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here: whoever placed the cache owns its settings.
    Otherwise the cache is ``<checkout>/.jax_cache`` and keeps every
    program, however fast it compiled — the engine's programs are many and
    small, most under JAX's default one-second threshold."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_DIR)


def listen() -> None:
    """Register the compile listener, once per process (idempotent)."""
    global _listening
    with _LOCK:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def counts() -> tuple[int, int]:
    """(programs built, of them persistent-cache hits) since :func:`listen`."""
    with _LOCK:
        return _programs, _hits


def _on_duration(event: str, duration: float, **kw) -> None:
    global _programs
    if event != BACKEND_COMPILE:
        return
    with _LOCK:
        _programs += 1
    from .core import config, schedule, trace

    ns = int(duration * 1e9)
    schedule.count("compiles")
    schedule.count("compile_ns", ns)
    tr = trace.current()
    if tr is not None and config.current_trace_ctx() is not None:
        # the build just ended on this thread: backdate the span to its start
        sp = tr.begin("compile", "compile")
        sp.t0 -= ns
        tr.end(sp)


def _on_event(event: str, **kw) -> None:
    global _hits
    if event == CACHE_HIT:
        with _LOCK:
            _hits += 1
