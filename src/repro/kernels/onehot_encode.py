"""One-hot encoding kernel (paper §2 A1 — ``get_dummies``).

Categorical codes (M,) → class-major indicator matrix (G, M) f32, built
tile-by-tile with a broadcasted-iota compare so the one-hot never
round-trips through HBM as int8 gather indices.  Code -1 (null) yields an
all-zero column.

Rows run along the 128-wide lane axis (codes arrive as (1, M)), so the codes
and each class's indicator row are lane-dense in HBM; an (M, 1) code column
or an (M, G) output with few classes would be padded to 128 lanes.

Grid: (M/TM, G/TG); each program writes one (TG, TM) output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import LANE, SUBLANE, cdiv, ceil_to, pad_axis, pick_tile, use_interpret


def _onehot_kernel(c_ref, o_ref, *, tg: int):
    j = pl.program_id(1)
    codes = c_ref[...]                       # (1, TM) int32
    seg = jax.lax.broadcasted_iota(jnp.int32, (tg, codes.shape[1]), 0) + j * tg
    o_ref[...] = (seg == codes).astype(o_ref.dtype)


def _onehot_padded(codes, num_classes: int, tm: int, tg: int):
    m = codes.shape[1]
    return pl.pallas_call(
        functools.partial(_onehot_kernel, tg=tg),
        grid=(cdiv(m, tm), cdiv(num_classes, tg)),
        in_specs=[pl.BlockSpec((1, tm), lambda i, j: (0, i))],
        out_specs=pl.BlockSpec((tg, tm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((num_classes, m), jnp.float32),
        interpret=use_interpret(),
    )(codes)


@functools.partial(jax.jit, static_argnames=("num_classes", "tile_m", "tile_g"))
def onehot_encode(codes: jnp.ndarray, num_classes: int, *,
                  tile_m: int = 2048, tile_g: int = 64) -> jnp.ndarray:
    """(M,) int32 codes → (num_classes, M) f32 one-hot (−1 → zero column)."""
    assert codes.ndim == 1
    m = codes.shape[0]
    if m == 0:
        return jnp.zeros((num_classes, 0), jnp.float32)
    tm = pick_tile(m, tile_m, LANE)
    tg = pick_tile(num_classes, tile_g, SUBLANE)
    cp = pad_axis(codes.astype(jnp.int32)[None, :], 1, ceil_to(m, tm), value=-1)
    out = _onehot_padded(cp, ceil_to(num_classes, tg), tm, tg)
    return out[:num_classes, :m]
