"""Operators (``physical.py``): milliseconds per statement in the groupby's
key step, the sum of the durations of the window's ``groupby:keys`` spans
(key hashing, factorization, decode and remap; per-block code arithmetic
on the dense-int path).  None where the window has no statement or no such
span (an engine without the span)."""


def read(w):
    ns = [s.dur for s in w.spans if s.name == "groupby:keys"]
    return sum(ns) / 1e6 / w.statements if w.statements and ns else None
