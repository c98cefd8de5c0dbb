"""Tiled VMEM transpose — the TRANSPOSE hot-spot (paper §4.2, Fig. 6).

The paper's "billions of columns" transpose is block-partitioned: each block
is transposed locally and the grid metadata is swapped.  This kernel is the
local per-block step, tiled so each (TM, TN) input tile is transposed inside
VMEM and written to the (TN, TM) mirrored output tile.

Grid: (M/TM, N/TN).  BlockSpecs:
  in : (TM, TN) tile at (i, j)
  out: (TN, TM) tile at (j, i)   ← the grid swap happens in the index_map

Tiles are LANE-aligned (128) on the last dim and SUBLANE-aligned (8) on the
second-to-last so the relayout uses full VREG shuffles on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import LANE, SUBLANE, cdiv, ceil_to, pad_axis, pick_tile, use_interpret


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def _transpose_padded(x: jnp.ndarray, tm: int, tn: int) -> jnp.ndarray:
    m, n = x.shape
    grid = (cdiv(m, tm), cdiv(n, tn))
    return pl.pallas_call(
        _transpose_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=use_interpret(),
    )(x)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n"))
def block_transpose(x: jnp.ndarray, *, tile_m: int = 256, tile_n: int = 256) -> jnp.ndarray:
    """Transpose a 2-D array with MXU/VPU-aligned VMEM tiles."""
    assert x.ndim == 2, x.shape
    m, n = x.shape
    if m == 0 or n == 0:
        return x.T
    tm = pick_tile(m, tile_m, SUBLANE)
    tn = pick_tile(n, tile_n, LANE)
    xp = pad_axis(pad_axis(x, 0, ceil_to(m, tm)), 1, ceil_to(n, tn))
    out = _transpose_padded(xp, tm, tn)
    return out[:n, :m]
