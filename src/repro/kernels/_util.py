"""Shared utilities for the Pallas TPU kernels.

All kernels target TPU (MXU 128×128, VPU lanes of 8×128, VMEM ~16 MiB/core)
and are *validated* on CPU via ``interpret=True``, which runs the kernel body
in Python.  ``use_interpret()`` flips automatically on non-TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# TPU-friendly tile granularities.
LANE = 128      # last-dim tiling (VREG lane count, MXU edge)
SUBLANE = 8     # second-to-last dim granularity for f32


@functools.lru_cache(maxsize=None)
def use_interpret() -> bool:
    """Pallas interpret mode on every backend but TPU, where the kernels
    always compile through Mosaic."""
    return jax.default_backend() != "tpu"


def ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_axis(x: jnp.ndarray, axis: int, target: int, value=0) -> jnp.ndarray:
    """Pad ``axis`` of x up to length ``target`` with ``value``."""
    cur = x.shape[axis]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=value)


def pick_tile(n: int, preferred: int, mult: int) -> int:
    """Largest multiple-of-``mult`` tile ≤ preferred that covers n sensibly."""
    if n <= mult:
        return mult
    t = min(preferred, ceil_to(n, mult))
    return max(mult, (t // mult) * mult)


# Storage dtype shims: Pallas TPU kernels operate on {f32, bf16, i32}; bools
# and narrow ints are widened at the wrapper boundary.
def widen_for_kernel(x: jnp.ndarray) -> tuple[jnp.ndarray, np.dtype]:
    orig = x.dtype
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32), orig
    if x.dtype in (jnp.int8, jnp.int16):
        return x.astype(jnp.int32), orig
    if x.dtype == jnp.float64:
        return x.astype(jnp.float32), orig
    return x, orig


def narrow_from_kernel(x: jnp.ndarray, orig: np.dtype) -> jnp.ndarray:
    if x.dtype != orig:
        return x.astype(orig)
    return x
