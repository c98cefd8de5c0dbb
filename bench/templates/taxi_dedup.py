"""A filtered dedup of the trips: the first trip of each distinct key among
the rows that pass one filter.

    trips[trips[filter] <op> threshold].drop_duplicates(subset)

Params: those of ``_taxi`` (the filter) and ``subset`` (the key columns).
The engine runs the block-parallel joint factorization
(``physical._drop_duplicates``); the reference is pandas'
``drop_duplicates(keep="first")``, labels included.
"""
from __future__ import annotations

import pandas as pd

from bench.templates import _taxi

SMALL = False
LIMITS = {"dedup_bad": 0}


def prepare(host, p):
    return _taxi.prepare(host, p)


def run(t, p):
    return _taxi.filtered(t, p).drop_duplicates(p["subset"]).collect()


def reference(host, p, lowp=False):
    tab, idx = _taxi.kept(host, p, lowp)
    keys = pd.DataFrame({n: _taxi.column(tab, n, idx) for n in p["subset"]},
                        index=pd.Index(idx))
    return _taxi.rows(tab, keys.drop_duplicates(keep="first").index.to_numpy())


def compare(got, want):
    return {"dedup_bad": _taxi.mismatches(got, want)}
