"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes × dtypes and ``assert_allclose`` each kernel (run with
``interpret=True`` on CPU) against these references.  The references are also
the execution path off-TPU (``ops.use_pallas``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "transpose", "segment_reduce", "window_scan", "linear_scan",
    "onehot_encode", "flash_attention", "decode_attention",
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
def linear_scan(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle for linear_scan: first-order recurrence h_t = a_t*h_{t-1} + b_t.

    a, b: (T, N).  Returns (T, N) of h_t.  This is the RG-LRU / SSM primitive.
    """
    if h0 is None:
        h0 = jnp.zeros_like(b[0])

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h0, (a, b))
    return hs


# -----------------------------------------------------------------------------
def onehot_encode(codes: jnp.ndarray, num_classes: int) -> jnp.ndarray:
    """Oracle for onehot_encode: (M,) int32 → class-major (G, M) f32; code -1
    → all-zero column."""
    classes = jnp.arange(num_classes, dtype=jnp.int32)[:, None]
    return (classes == codes[None, :]).astype(jnp.float32)


# -----------------------------------------------------------------------------
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    window: int | None = None) -> jnp.ndarray:
    """Oracle attention.  q,k,v: (H, S, D) (single sequence, multi-head) or
    (S, D).  GQA handled by the wrapper (repeating kv heads).  ``window``:
    local attention span (keys within [i-window+1, i])."""
    single = q.ndim == 2
    if single:
        q, k, v = q[None], k[None], v[None]
    h, sq, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # align ends (decode-style)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32))
    out = out.astype(q.dtype)
    return out[0] if single else out


# -----------------------------------------------------------------------------
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     length: int, scale: float | None = None) -> jnp.ndarray:
    """Oracle single-token GQA decode attention.

    q: (H, D) one new token's query heads; k_cache/v_cache: (S, KVH, D);
    ``length``: number of valid cache slots.  H = KVH * group.
    """
    h, d = q.shape
    s, kvh, _ = k_cache.shape
    group = h // kvh
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(kvh, group, d).astype(jnp.float32)
    kk = k_cache.astype(jnp.float32)
    vv = v_cache.astype(jnp.float32)
    logits = jnp.einsum("kgd,skd->kgs", qg, kk) * scale
    valid = (jnp.arange(s) < length)[None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("kgs,skd->kgd", p, vv)
    return out.reshape(h, d).astype(q.dtype)
