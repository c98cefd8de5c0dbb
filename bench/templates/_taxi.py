"""What the ``taxi_*`` templates share: the filter each statement starts
with, the reference's row gathers and the row-exact comparison.

Params of every ``taxi_*`` statement: ``table``, ``filter`` (a column),
``op`` (``>=`` or ``<``) and ``q``: the statement keeps the rows whose
``filter`` value stands in ``op`` to the column's ``q`` quantile, taken as a
stored value (``method="higher"``), so the literal is exact in float32.
"""
from __future__ import annotations

import operator

import numpy as np

from bench.check import bad

OPS = {">=": operator.ge, "<": operator.lt}


def prepare(host, p):
    v = host.table(p["table"]).data[p["filter"]]
    return dict(p, threshold=float(np.quantile(v, p["q"], method="higher")))


def filtered(t, p):
    """The engine's side: the table's rows that pass the statement's filter."""
    df = t[p["table"]]
    return df[OPS[p["op"]](df[p["filter"]], p["threshold"])]


def kept(host, p, lowp):
    """The reference's side: the table (bfloat16 floats with ``lowp``) and
    the positions of the rows that pass the filter."""
    tab = host.table(p["table"], lowp)
    v = tab.data[p["filter"]]
    return tab, np.nonzero(OPS[p["op"]](v, v.dtype.type(p["threshold"])))[0]


def column(tab, name, idx):
    """Rows ``idx`` of one column as ``Table.values`` gives them (the taxi
    tables have no nulls): float64, coded columns decoded to objects."""
    v = tab.data[name][idx]
    if name in tab.labels:
        return np.asarray(tab.labels[name], dtype=object)[v]
    return v.astype(np.float64)


def rows(tab, idx):
    """Rows ``idx`` of the table as a pandas frame labelled ``idx``."""
    import pandas as pd
    return pd.DataFrame({n: column(tab, n, idx) for n in tab.data},
                        index=pd.Index(idx))


def _nulls_as_none(v):
    """Object columns with every null (None or NaN) as None."""
    v = np.asarray(v)
    if v.dtype != object:
        return v
    import pandas as pd
    out = v.copy()
    out[pd.isna(v)] = None
    return out


def mismatches(got, want) -> int:
    """Rows, columns, row order, values, nulls and row labels of ``got`` (a
    ``check.Frame``) that differ from the reference frame ``want``; a
    different shape or column list counts every row."""
    if got.rows != len(want) or list(got.names) != list(want.columns):
        return int(max(got.rows, len(want), 1))
    n = bad(np.asarray(got.labels, np.float64), want.index.to_numpy(np.float64))
    for c in want.columns:
        n += bad(_nulls_as_none(got[c]), _nulls_as_none(want[c].to_numpy()))
    return int(n)
