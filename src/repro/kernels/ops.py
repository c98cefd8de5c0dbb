"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy:
  * On TPU — always the Pallas kernels, compiled by Mosaic; no environment
    variable can swap in the references or interpret mode there.
  * Elsewhere — the pure-jnp references by default (XLA:CPU fuses them well
    and the interpret-mode emulation is for *validation*, not speed); set
    ``REPRO_USE_KERNELS=1`` to run the kernels in interpret mode instead.

Every wrapper has an identically-shaped oracle in ``ref.py``; tests sweep
shapes × dtypes asserting allclose between the two.

The entry points the engine calls (``segment_reduce_multi``, ``window_scan``,
``onehot_encode``, ``transpose``) count their host numpy operands in
``ExecStats.h2d_bytes`` (``core.transfer.note_h2d``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..core.transfer import note_h2d
from . import ref
from .block_transpose import block_transpose as _pallas_transpose
from .decode_attention import decode_attention as _pallas_decode
from .flash_attention import flash_attention as _pallas_flash
from .linear_scan import linear_scan as _pallas_linscan
from .onehot_encode import onehot_encode as _pallas_onehot
from .segment_reduce import segment_reduce as _pallas_segred
from .window_scan import window_scan as _pallas_winscan
from ._util import narrow_from_kernel, widen_for_kernel

__all__ = [
    "use_pallas", "transpose", "segment_reduce", "segment_reduce_multi",
    "window_scan", "linear_scan", "onehot_encode", "flash_attention",
    "decode_attention",
]


def use_pallas() -> bool:
    if jax.default_backend() == "tpu":
        return True
    return os.environ.get("REPRO_USE_KERNELS", "0") not in ("0", "")


# -----------------------------------------------------------------------------
def transpose(x: jnp.ndarray) -> jnp.ndarray:
    note_h2d(x)
    if use_pallas():
        w, orig = widen_for_kernel(x)
        return narrow_from_kernel(_pallas_transpose(w), orig)
    return ref.transpose(x)


def segment_reduce(values, codes, num_segments: int, op: str = "sum"):
    if use_pallas():
        return _pallas_segred(values, codes, num_segments, op)
    return ref.segment_reduce(values.astype(jnp.float32), codes, num_segments, op)


@functools.partial(jax.jit, static_argnames=("bases", "num_segments",
                                             "presence", "pallas"))
def _segment_reduce_multi_prog(vals, valids, codes, *, bases: tuple,
                               num_segments: int, presence: bool, pallas: bool):
    by_op: dict[str, list] = {}

    def put(op: str, pos: int, vec) -> None:
        by_op.setdefault(op, []).append((pos, vec))

    for i, base in enumerate(bases):
        v = vals[i].astype(jnp.float32)
        valid = valids[i]
        if valid is None:
            valid = jnp.ones(v.shape[0], jnp.bool_)
        if base == "count":
            put("sum", i, valid.astype(jnp.float32))
        elif base == "sum":
            put("sum", i, jnp.where(valid, v, 0.0))
        elif base == "sumsq":
            put("sum", i, jnp.where(valid, v * v, 0.0))
        elif base == "min":
            put("min", i, jnp.where(valid, v, jnp.finfo(jnp.float32).max))
        else:   # max
            put("max", i, jnp.where(valid, v, jnp.finfo(jnp.float32).min))
    if presence:
        # segment presence = #rows with a valid (non-negative) code,
        # independent of value nulls
        put("sum", len(bases), jnp.ones(codes.shape[0], jnp.float32))

    out: list = [None] * (len(bases) + (1 if presence else 0))
    for op, items in by_op.items():
        if len(items) == 1:
            out[items[0][0]] = segment_reduce(items[0][1], codes, num_segments, op)
        else:
            mat = jnp.stack([vec for _, vec in items], axis=1)
            res = segment_reduce(mat, codes, num_segments, op)
            for j, (pos, _) in enumerate(items):
                out[pos] = res[:, j]
    return tuple(out)


def segment_reduce_multi(vals, valids, codes, *, bases, num_segments: int,
                         presence: bool = False):
    """A whole per-block partial-aggregation stage as ONE compiled program:
    null masking, squaring, presence counting, and one ``segment_reduce`` per
    reduce op, with same-op columns stacked into the kernel's (M, C)
    multi-column batch.  ``bases[i]`` ∈ {sum,count,sumsq,min,max} names the
    statistic computed from ``(vals[i], valids[i])``; ``valids[i]`` may be
    None (all valid).  Returns one (G,)-vector per base, plus a trailing
    presence vector when ``presence``.  Eager per-op dispatch of the same
    graph was the dominant cost of the groupby hot path on the shared pool.

    ``pallas`` enters the jit cache key so a kernel-dispatch env flip between
    calls can't serve a program traced for the other mode."""
    note_h2d(*vals, *valids, codes)
    return _segment_reduce_multi_prog(
        list(vals), list(valids), jnp.asarray(codes, jnp.int32),
        bases=tuple(bases), num_segments=num_segments, presence=presence,
        pallas=use_pallas())


def window_scan(x, op: str = "cumsum"):
    note_h2d(x)
    if use_pallas():
        return _pallas_winscan(x, op)
    return ref.window_scan(x.astype(jnp.float32), op)


def linear_scan(a, b):
    if use_pallas():
        return _pallas_linscan(a, b)
    return ref.linear_scan(a.astype(jnp.float32), b.astype(jnp.float32))


def onehot_encode(codes, num_classes: int):
    note_h2d(codes)
    if use_pallas():
        return _pallas_onehot(codes, num_classes)
    return ref.onehot_encode(codes, num_classes)


def flash_attention(q, k, v, *, causal: bool = True, scale=None, window=None):
    if use_pallas():
        return _pallas_flash(q, k, v, causal=causal, scale=scale, window=window)
    return ref.flash_attention(q, k, v, causal=causal, scale=scale, window=window)


def decode_attention(q, k_cache, v_cache, length, *, scale=None):
    if use_pallas():
        return _pallas_decode(q, k_cache, v_cache, length, scale=scale)
    return ref.decode_attention(q, k_cache, v_cache, length, scale=scale)
