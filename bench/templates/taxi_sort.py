"""A filtered sort of the trips: the rows that pass one filter, ordered by
one or more columns, stably (ties keep the table's order), NaN last.

    trips[trips[filter] <op> threshold].sort_values(by, ascending)

Params: those of ``_taxi`` (the filter), ``by`` (a list of columns) and
``ascending``.  The engine runs the sample-sort exchange
(``shuffle.shuffled_sort``).
"""
from __future__ import annotations

import numpy as np

from bench.templates import _taxi

SMALL = False                # up to millions of rows: one round is checked
LIMITS = {"sort_bad": 0}


def prepare(host, p):
    return _taxi.prepare(host, p)


def run(t, p):
    return _taxi.filtered(t, p).sort_values(p["by"], ascending=p["ascending"]).collect()


def reference(host, p, lowp=False):
    tab, idx = _taxi.kept(host, p, lowp)
    keys = []                # np.lexsort: the last key is the first compared
    for name in reversed(p["by"]):
        v = _taxi.column(tab, name, idx)
        nan = np.isnan(v)
        keys += [np.where(nan, 0.0, v if p["ascending"] else -v), nan]
    return _taxi.rows(tab, idx[np.lexsort(keys)])


def compare(got, want):
    return {"sort_bad": _taxi.mismatches(got, want)}
