"""Host↔device transfer counters: every copy between the host and the
device that the engine makes goes through these helpers, which bump
``ExecStats.d2h_bytes`` / ``d2h_copies`` / ``h2d_bytes`` of the plan node
being evaluated (``schedule.count``; nothing outside a node).

Row takes, concats and key work run as host numpy (``frame.Column.take``).
A registered device table's blocks stay on the device
(``frame.Frame.split_rows``): device programs read them where they are,
and a row take or key read fetches each block column's host copy once and
caches it on the array, so only the first statement to read a column moves
it.  These counters say how much moves, per statement and per node span.
"""
from __future__ import annotations

import functools
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .schedule import count

__all__ = ["to_host", "to_device", "note_h2d"]

# id → weakref of each device array already read to the host: JAX caches a
# device array's host copy on the array, so only its first read moves bytes
_FETCHED: dict[int, weakref.ref] = {}
_FETCHED_LOCK = threading.RLock()   # a weakref callback can run under it


def _forget(key: int, ref: weakref.ref) -> None:
    with _FETCHED_LOCK:
        if _FETCHED.get(key) is ref:
            del _FETCHED[key]


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting a device array's first read: its
    bytes in ``d2h_bytes`` and one ``d2h_copies`` (the host blocked on it).
    A host array, or a device array read before, counts nothing.  On a
    backend that shares host memory (CPU) the count is of what a device
    backend would move."""
    if not isinstance(x, jax.Array):
        return np.asarray(x, dtype=dtype)
    key = id(x)
    with _FETCHED_LOCK:
        ref = _FETCHED.get(key)
        seen = ref is not None and ref() is x
    out = np.asarray(x)
    if not seen:
        with _FETCHED_LOCK:
            _FETCHED[key] = weakref.ref(x, functools.partial(_forget, key))
        count("d2h_bytes", out.nbytes)
        count("d2h_copies")
    return out if dtype is None else out.astype(dtype, copy=False)


def note_h2d(*arrays) -> None:
    """Count the host numpy arrays among the operands of a device program
    (each array once) in ``h2d_bytes``; device arrays and scalars count
    nothing."""
    seen: set[int] = set()
    n = 0
    for a in arrays:
        if isinstance(a, np.ndarray) and id(a) not in seen:
            seen.add(id(a))
            n += a.nbytes
    if n:
        count("h2d_bytes", n)


def to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, counting a host array's bytes."""
    note_h2d(x)
    return jnp.asarray(x, dtype=dtype)
