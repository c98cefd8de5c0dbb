"""WINDOW operator kernel: ordered cumulative functions (paper §3.3, §4.2).

WINDOW "does not admit row-wise parallelism because computation for each
subsequent row must wait for the result of the prior row" (paper §4.2).  The
TPU-native resolution: a *blocked scan* — each tile computes its local
cumulative in VMEM (log-depth on the VPU), then a running carry scratch
bridges tiles across the sequential grid.  Cross-shard composition is a short
exclusive scan over per-shard totals (see physical.py), preserving exact
ordered semantics with parallel execution — the paper's WINDOW-parallelism
challenge resolved.

Layout: each column's M values are laid out lane-dense as (R, 128) in
row-major order (value i at row i // 128, lane i % 128), so a single column
costs no lane padding in HBM.  A (TR, 128) tile scans in two log-step
shift-and-combine passes built from ``pltpu.roll`` (Mosaic has no lowering
for ``cumsum`` / ``cummax`` / ``cummin``): an inclusive scan along the lanes
of every row, then an inclusive scan of the row totals down the sublanes,
whose exclusive form plus the carry of earlier tiles completes each value.
Several columns run as the outer ("parallel") grid axis, each with its own
carry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import LANE, SUBLANE, cdiv, ceil_to, pad_axis, pick_tile, use_interpret

_OPS = ("cumsum", "cummax", "cummin")
_IDENTITY = {"cumsum": 0.0, "cummax": -jnp.inf, "cummin": jnp.inf}
_REDUCE = {"cumsum": jnp.sum, "cummax": jnp.max, "cummin": jnp.min}


def _combine(op: str, a, b):
    if op == "cumsum":
        return a + b
    if op == "cummax":
        return jnp.maximum(a, b)
    return jnp.minimum(a, b)


def _inclusive_scan(x, op: str, axis: int):
    """Hillis–Steele scan along ``axis`` of a 2-D tile: log2(len) rounds of
    roll-by-2^k, masked so nothing wraps around from the far end."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = jnp.where(pos >= shift,
                      _combine(op, x, pltpu.roll(x, shift, axis)), x)
        shift *= 2
    return x


def _scan_kernel(x_ref, o_ref, carry_ref, *, op: str):
    i = pl.program_id(1)
    ident = _IDENTITY[op]

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.full_like(carry_ref, ident)

    x = x_ref[...].astype(jnp.float32)                  # (TR, LANE)
    rows = _inclusive_scan(x, op, axis=1)               # prefix within a row
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    last = jnp.where(lane == x.shape[1] - 1, rows, ident)
    totals = _REDUCE[op](last, axis=1, keepdims=True)   # each row's total
    totals = _inclusive_scan(jnp.broadcast_to(totals, x.shape), op, axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    before = jnp.where(row >= 1, pltpu.roll(totals, 1, 0), ident)
    carry = carry_ref[...]                              # (1, LANE), lanes equal
    o_ref[...] = _combine(op, _combine(op, rows, before), carry).astype(o_ref.dtype)
    carry_ref[...] = _combine(op, carry, totals[-1:, :])


def _window_scan_padded(x, op: str, tr: int):
    n, r, lanes = x.shape
    return pl.pallas_call(
        functools.partial(_scan_kernel, op=op),
        grid=(n, cdiv(r, tr)),
        in_specs=[pl.BlockSpec((pl.Squeezed(), tr, lanes), lambda j, i: (j, i, 0))],
        out_specs=pl.BlockSpec((pl.Squeezed(), tr, lanes), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=use_interpret(),
    )(x)


@functools.partial(jax.jit, static_argnames=("op", "tile_rows"))
def window_scan(x: jnp.ndarray, op: str = "cumsum", *, tile_rows: int = 256) -> jnp.ndarray:
    """Cumulative ``op`` along axis 0 of (M,) or (M, N) values (f32 out), as
    one compiled program per shape (layout, padding and kernel together)."""
    assert op in _OPS, op
    squeeze = x.ndim == 1
    v = (x[None, :] if squeeze else x.T).astype(jnp.float32)   # (N, M)
    n, m = v.shape
    if m == 0:
        return x.astype(jnp.float32)
    tr = pick_tile(cdiv(m, LANE), tile_rows, SUBLANE)
    r = ceil_to(cdiv(m, LANE), tr)
    vp = pad_axis(v, 1, r * LANE, value=_IDENTITY[op]).reshape(n, r, LANE)
    out = _window_scan_padded(vp, op, tr).reshape(n, r * LANE)[:, :m]
    return out[0] if squeeze else out.T
