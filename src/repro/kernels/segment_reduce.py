"""GROUPBY aggregation as one-hot matmul on the MXU (paper §4.2, Fig. 6).

Hardware adaptation (DESIGN.md §3): TPUs have no efficient scatter, so hash
aggregation is re-thought as dense linear algebra.  For a tile of TM rows with
group codes c ∈ [0, G), build the one-hot matrix H ∈ {0,1}^(TG×TM) on the fly
(broadcasted-iota compare — never materialized in HBM) and compute

    partial  +=  H · valuesᵀ                         (sum / count, MXU)
    partial   =  min/max(where(H, v_col, ±max))      one column at a time,
                 reduced along the row (lane) axis    on the VPU

Layout: rows run along the 128-wide lane axis — values arrive as (C, M) and
codes as (1, M) — so a single column or the code vector is lane-dense in HBM
instead of an (M, 1) array padded to 128 lanes (128× its size).  Segments sit
on the sublane axis, so a handful of groups costs a tile of 8, not 128.

Grid: (G/TG, M/TM) with the *segment* axis outermost so each output tile stays
resident in VMEM while the full M axis streams through (sequential-grid
accumulation).  A single psum across row shards combines partials — this is
what turns the paper's groupby shuffle into an aggregate-sized all-reduce.

The sum matmul runs at ``Precision.HIGHEST``: the MXU's default single bf16
pass would round every value to 8 significant bits before accumulating.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import LANE, SUBLANE, cdiv, ceil_to, pad_axis, pick_tile, use_interpret


def _seg_kernel(v_ref, c_ref, o_ref, *, op: str, tg: int):
    j = pl.program_id(0)   # segment tile (outer — output stays in VMEM)
    i = pl.program_id(1)   # row tile (inner — streams through)
    big = jnp.finfo(jnp.float32).max
    fill = {"sum": 0.0, "count": 0.0, "min": big, "max": -big}[op]

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, fill)

    codes = c_ref[...]                                   # (1, TM) int32
    seg = jax.lax.broadcasted_iota(jnp.int32, (tg, codes.shape[1]), 0) + j * tg
    onehot = seg == codes                                # (TG, TM); -1 never matches
    v = v_ref[...].astype(jnp.float32)                   # (C, TM)

    if op in ("sum", "count"):
        contrib = jnp.ones_like(v) if op == "count" else v
        contrib = jnp.where(codes >= 0, contrib, 0.0)
        # MXU path: (TG, TM) · (C, TM)ᵀ → (TG, C)
        o_ref[...] += jax.lax.dot_general(
            onehot.astype(jnp.float32), contrib,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return

    reduce = jnp.min if op == "min" else jnp.max
    col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    part = jnp.full(o_ref.shape, fill, jnp.float32)
    for k in range(v.shape[0]):
        masked = jnp.where(onehot, v[k:k + 1, :], fill)          # (TG, TM)
        part = jnp.where(col == k, reduce(masked, axis=1, keepdims=True), part)
    o_ref[...] = (jnp.minimum if op == "min" else jnp.maximum)(o_ref[...], part)


def _segment_reduce_padded(values, codes, num_segments: int, op: str, tm: int, tg: int):
    c, m = values.shape
    grid = (cdiv(num_segments, tg), cdiv(m, tm))
    return pl.pallas_call(
        functools.partial(_seg_kernel, op=op, tg=tg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, tm), lambda j, i: (0, i)),
            pl.BlockSpec((1, tm), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((tg, c), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=use_interpret(),
    )(values, codes)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "op", "tile_m", "tile_g"))
def segment_reduce(values: jnp.ndarray, codes: jnp.ndarray, num_segments: int,
                   op: str = "sum", *, tile_m: int = 2048, tile_g: int = 128) -> jnp.ndarray:
    """Per-segment aggregate.  values (M,) or (M,C) f32; codes (M,) int32 with
    -1 = null (contributes nothing).  Returns (G,) or (G,C) f32."""
    assert op in ("sum", "count", "min", "max"), op
    squeeze = values.ndim == 1
    v = (values[None, :] if squeeze else values.T).astype(jnp.float32)   # (C, M)
    m = v.shape[1]
    if m == 0:
        from . import ref
        return ref.segment_reduce(values.astype(jnp.float32), codes,
                                  num_segments, op)
    tm = pick_tile(m, tile_m, LANE)
    tg = pick_tile(num_segments, tile_g, SUBLANE)
    g_pad = ceil_to(num_segments, tg)
    vp = pad_axis(v, 1, ceil_to(m, tm))
    cp = pad_axis(codes.astype(jnp.int32)[None, :], 1, ceil_to(m, tm), value=-1)
    out = _segment_reduce_padded(vp, cp, g_pad, op, tm, tg)[:num_segments]
    return out[:, 0] if squeeze else out
