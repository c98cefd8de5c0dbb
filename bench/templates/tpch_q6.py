"""TPC-H Q6, the forecasting revenue change (Specification 3.0.1, clause
2.4.6).

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date 'YEAR-01-01'
      and l_shipdate < date 'YEAR-01-01' + interval '1' year
      and l_discount between DISCOUNT - 0.01 and DISCOUNT + 0.01
      and l_quantity < QUANTITY

Params: ``table``, ``year`` (1993 to 1997), ``discount`` (0.02 to 0.09),
``quantity`` (24 or 25).  The discount bounds are compared as the float32
values the column holds.
"""
from __future__ import annotations

import datetime

import numpy as np

from bench.check import err, lowp as round_lowp

SMALL = True
LIMITS = {"q6_err": 2e-5}


def _days(year: int) -> int:
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def prepare(host, p):
    return dict(p, lo=_days(p["year"]), hi=_days(p["year"] + 1),
                dlo=float(np.float32(p["discount"] - 0.01)),
                dhi=float(np.float32(p["discount"] + 0.01)))


def run(t, p):
    li = t[p["table"]]
    f = li[(li["l_shipdate"] >= p["lo"]) & (li["l_shipdate"] < p["hi"])
           & (li["l_discount"] >= p["dlo"]) & (li["l_discount"] <= p["dhi"])
           & (li["l_quantity"] < p["quantity"])]
    f["revenue"] = f["l_extendedprice"] * f["l_discount"]
    return f["revenue"].sum()


def reference(host, p, lowp=False):
    li = host.table(p["table"], lowp)
    d = li.data
    keep = ((d["l_shipdate"] >= p["lo"]) & (d["l_shipdate"] < p["hi"])
            & (d["l_discount"] >= np.float32(p["dlo"]))
            & (d["l_discount"] <= np.float32(p["dhi"]))
            & (d["l_quantity"] < p["quantity"]))
    rev = d["l_extendedprice"][keep].astype(np.float64) * d["l_discount"][keep]
    return float((round_lowp(rev) if lowp else rev).sum())


def compare(got, want):
    """The revenue against its own size (a sum of non-negative values)."""
    return {"q6_err": err(got, want, abs(want))}
