"""Compile (JAX): XLA programs that set-up built, compiled or loaded from the
persistent cache, before the window (``jax.monitoring``'s backend-compile
events).  The engine builds programs per filtered length, so this counts
what set-up pays for the traffic's shapes; it moves ``setup_s``."""


def read(w):
    return float(w.setup_compiles[0])
