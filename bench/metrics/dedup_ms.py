"""Operators (``physical.py``): milliseconds per statement in the
block-parallel DROP-DUPLICATES / DIFFERENCE path, the summed durations of
the window's ``dedup:keys`` (per-block key extraction with the absorbed
filter), ``dedup:ids`` (joint factorization and first occurrences or the
anti-join test) and ``dedup:keep`` (the blockwise keep-mask filter) spans.
None where the window has no statement or no such span (an engine without
the spans)."""

NAMES = frozenset(("dedup:keys", "dedup:ids", "dedup:keep"))


def read(w):
    ns = [s.dur for s in w.spans if s.name in NAMES]
    return sum(ns) / 1e6 / w.statements if w.statements and ns else None
