"""A filtered merge of the trips with the rate-code table.

    trips[trips[filter] <op> threshold].merge(rate_codes, on=on, how=how)

Params: those of ``_taxi`` (the filter), ``right`` (the lookup table),
``on`` and ``how`` (``inner`` or ``left``; a left merge keeps the trips
whose code the table lacks, with a null name).  The engine runs the
grace-hash exchange (``shuffle.shuffled_join``); the reference is
``pandas.merge``, whose row order and labels the answer must have.
"""
from __future__ import annotations

import numpy as np

from bench.templates import _taxi

SMALL = False
LIMITS = {"merge_bad": 0}


def prepare(host, p):
    return _taxi.prepare(host, p)


def run(t, p):
    return _taxi.filtered(t, p).merge(t[p["right"]], on=p["on"], how=p["how"]).collect()


def reference(host, p, lowp=False):
    tab, idx = _taxi.kept(host, p, lowp)
    right = host.table(p["right"], lowp)
    return _taxi.rows(tab, idx).merge(
        _taxi.rows(right, np.arange(right.rows)), on=p["on"], how=p["how"])


def compare(got, want):
    return {"merge_bad": _taxi.mismatches(got, want)}
