"""Bytes each kernel program must move for the work of one call, counted
from the call's shapes (recorded by ``harness.KernelCalls``).  Only the
work counts: the operands read once and the results written once, never
what the implementation adds (the one-hot matrix of the groupby's matmul,
padding, layout copies).  So a roofline share reads the same work whatever
implements it.

A recorded argument is ``(shape, dtype, id)``; ``id`` tells one array passed
twice (a column with both a sum and a count) from two arrays."""
from __future__ import annotations

import numpy as np


def _nbytes(leaf) -> int:
    shape, dtype, _ = leaf
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def groupby_partial(call) -> int:
    """``_segment_reduce_multi_prog(vals, valids, codes, *, bases,
    num_segments, presence, ...)``: read every distinct value column (its
    stored width, 4 B), validity mask (1 B) and the codes (4 B) once per row,
    and write ``num_segments`` float32 results per statistic."""
    (vals, valids, codes), kw = call
    seen = {}
    for leaf in list(vals) + [v for v in valids if v is not None] + [codes]:
        seen[leaf[2]] = _nbytes(leaf)
    stats = len(kw["bases"]) + (1 if kw["presence"] else 0)
    return sum(seen.values()) + kw["num_segments"] * stats * 4

