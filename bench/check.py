"""The comparison that decides ``correct``: engine results against the plain
reference, as numbers that each have a limit.

Two kinds of number.  ``*_bad`` counts elements that differ where the answer
is exact (keys, counts, group order); its limit is 0.  ``*_err`` is the
widest gap of a float sum or mean, as a share of the sum of absolute values
that produced it (the scale float32 rounding works against); its limit is
set between the program's readings and the control's (``PERF.md`` gives
both).

The control is the reference computed in bfloat16 (:func:`lowp`), the next
precision below the configurations' float32.
"""
from __future__ import annotations

import numpy as np


def lowp(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 and held as float32: the control's values."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class Frame:
    """Host view of an engine ``Frame``: float64 columns with NaN at nulls,
    coded columns decoded to objects with None at nulls, and row labels."""

    def __init__(self, frame):
        self.names = list(frame.col_labels.to_list())
        self.cols = {}
        for name, c in zip(self.names, frame.columns):
            v = np.asarray(c.data)
            m = None if c.mask is None else np.asarray(c.mask)
            if c.dictionary is not None:
                out = np.asarray(c.dictionary, dtype=object)[np.clip(v, 0, None)] \
                    if len(c.dictionary) else np.full(v.shape, None, object)
                bad = v < 0 if m is None else (v < 0) | ~m
                if bad.any():
                    out = out.copy()
                    out[bad] = None
            else:
                out = v.astype(np.float64)
                if m is not None:
                    out[~m] = np.nan
            self.cols[name] = out
        rl = frame.row_labels
        if hasattr(rl, "start"):
            self.labels = np.arange(rl.start, rl.start + len(rl))
        elif hasattr(rl, "values"):
            self.labels = np.asarray(rl.values)
        else:
            self.labels = np.asarray(rl.to_list())
        self.rows = frame.nrows

    def __getitem__(self, name):
        return self.cols[name]


def bad(got, want) -> int:
    """Elements of ``got`` that differ from ``want`` (NaN equals NaN, None
    equals None); a length mismatch counts every element of the longer."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size, 1))
    if got.dtype == object or want.dtype == object:
        # coded columns decode to str with None at nulls
        return int((~np.asarray(got == want, dtype=bool)).sum())
    g = got.astype(np.float64)
    w = want.astype(np.float64)
    return int((~((g == w) | (np.isnan(g) & np.isnan(w)))).sum())


def err(got, want, scale) -> float:
    """Widest ``|got - want| / scale``; a null on one side only, or a length
    mismatch, reads as 1 (the whole scale)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return 1.0
    scale = np.broadcast_to(np.asarray(scale, np.float64), got.shape)
    gn, wn = np.isnan(got), np.isnan(want)
    if (gn != wn).any():
        return 1.0
    ok = ~wn
    if not ok.any():
        return 0.0
    gap = np.abs(got[ok] - want[ok]) / np.maximum(scale[ok], 1e-30)
    return float(gap.max())


def from_pandas(pdf) -> Frame:
    """The control's answer (a pandas frame) in the same host view."""
    out = Frame.__new__(Frame)
    out.names = list(pdf.columns)
    out.cols = {}
    for name in out.names:
        v = pdf[name].to_numpy()
        out.cols[name] = v if v.dtype == object else v.astype(np.float64)
    out.labels = pdf.index.to_numpy()
    out.rows = len(pdf)
    return out


def view(result):
    """An engine result, or the control's pandas answer, as comparable host
    values: a :class:`Frame`, or a float for a scalar."""
    if hasattr(result, "col_labels"):
        return Frame(result)
    if hasattr(result, "columns"):
        return from_pandas(result)
    return float(result)
