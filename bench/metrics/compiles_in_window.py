"""Compile (JAX): XLA programs built inside the window, compiled or loaded
from the persistent cache (``jax.monitoring``'s backend-compile events,
which a cache load fires too).  Every shape is warmed up in set-up, so this
should read 0."""


def read(w):
    return float(w.compiles[0])
