"""Persistent compilation cache for the entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/*.py`` call
:func:`enable` before their first JAX computation; importing ``repro`` never
does, so the tests write no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path: the cache is only found again if every run uses the same one
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


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
