"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes × dtypes and ``assert_allclose`` each kernel (run with
``interpret=True`` on CPU) against these references.  The references are also
the execution path off-TPU (``ops.use_pallas``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "transpose", "segment_reduce", "window_scan", "onehot_encode",
]


# -----------------------------------------------------------------------------
def transpose(x: jnp.ndarray) -> jnp.ndarray:
    """Oracle for block_transpose: plain 2-D transpose."""
    return x.T


# -----------------------------------------------------------------------------
def segment_reduce(values: jnp.ndarray, codes: jnp.ndarray, num_segments: int,
                   op: str = "sum") -> jnp.ndarray:
    """Oracle for segment_reduce: per-segment aggregate of ``values``.

    values: (M,) or (M, C) float32; codes: (M,) int32 in [-1, G).  Code -1
    (null/padding) contributes nothing.  Returns (G,) or (G, C).
    """
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    valid = codes >= 0
    safe = jnp.where(valid, codes, 0)
    if op == "sum":
        out = jax.ops.segment_sum(jnp.where(valid[:, None], v, 0.0), safe, num_segments)
    elif op == "count":
        ones = jnp.where(valid[:, None], 1.0, 0.0) * jnp.ones_like(v)
        out = jax.ops.segment_sum(ones, safe, num_segments)
    elif op == "min":
        big = jnp.asarray(jnp.finfo(v.dtype).max, v.dtype)
        out = jax.ops.segment_min(jnp.where(valid[:, None], v, big), safe, num_segments)
    elif op == "max":
        small = jnp.asarray(jnp.finfo(v.dtype).min, v.dtype)
        out = jax.ops.segment_max(jnp.where(valid[:, None], v, small), safe, num_segments)
    else:
        raise ValueError(op)
    return out[:, 0] if squeeze else out


# -----------------------------------------------------------------------------
def window_scan(x: jnp.ndarray, op: str = "cumsum") -> jnp.ndarray:
    """Oracle for window_scan: ordered cumulative op along axis 0 of (M, N)."""
    if op == "cumsum":
        return jnp.cumsum(x, axis=0)
    if op == "cummax":
        return jax.lax.cummax(x, axis=0)
    if op == "cummin":
        return jax.lax.cummin(x, axis=0)
    raise ValueError(op)


# -----------------------------------------------------------------------------
def onehot_encode(codes: jnp.ndarray, num_classes: int) -> jnp.ndarray:
    """Oracle for onehot_encode: (M,) int32 → class-major (G, M) f32; code -1
    → all-zero column."""
    classes = jnp.arange(num_classes, dtype=jnp.int32)[:, None]
    return (classes == codes[None, :]).astype(jnp.float32)
