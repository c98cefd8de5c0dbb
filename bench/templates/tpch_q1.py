"""TPC-H Q1, the pricing summary report (Specification 3.0.1, clause 2.4.1).

    select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval DELTA day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

Params: ``table``, ``delta`` (days, 60 to 120).
"""
from __future__ import annotations

import numpy as np

from bench.check import bad, err, lowp as round_lowp

SMALL = True                 # four rows: every answer of the window is checked
LIMITS = {"q1_err": 2e-5, "q1_bad": 0}
LAST_DAY = 10561             # 1998-12-01, days since 1970-01-01
SPEC = {"l_quantity": ["sum", "mean"], "l_extendedprice": ["sum", "mean"],
        "disc_price": ["sum"], "charge": ["sum"], "l_discount": ["mean"],
        "l_orderkey": ["count"]}
KEYS = ["l_returnflag", "l_linestatus"]


def prepare(host, p):
    return p


def run(t, p):
    li = t[p["table"]]
    f = li[li["l_shipdate"] <= LAST_DAY - p["delta"]]
    f["disc_price"] = f["l_extendedprice"] * (f["l_discount"] * -1.0 + 1.0)
    f["charge"] = f["disc_price"] * (f["l_tax"] + 1.0)
    return f.groupby(KEYS).agg(SPEC).sort_values(KEYS).collect()


def reference(host, p, lowp=False):
    import pandas as pd
    li = host.table(p["table"], lowp)
    keep = li.data["l_shipdate"] <= LAST_DAY - p["delta"]
    col = {n: li.data[n][keep].astype(np.float64)
           for n in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")}
    r = round_lowp if lowp else (lambda x: x)
    col["disc_price"] = r(col["l_extendedprice"] * r(1.0 - col["l_discount"]))
    col["charge"] = r(col["disc_price"] * r(1.0 + col["l_tax"]))
    nls = len(li.labels["l_linestatus"])
    group = li.data["l_returnflag"][keep] * nls + li.data["l_linestatus"][keep]
    size = len(li.labels["l_returnflag"]) * nls
    count = np.bincount(group, minlength=size)
    present = np.nonzero(count)[0]
    out = {"l_returnflag": np.asarray(li.labels["l_returnflag"], object)[present // nls],
           "l_linestatus": np.asarray(li.labels["l_linestatus"], object)[present % nls]}
    for c, fns in SPEC.items():
        if c == "l_orderkey":
            out["l_orderkey_count"] = count[present].astype(np.float64)
            continue
        s = np.bincount(group, weights=col[c], minlength=size)[present]
        for fn in fns:
            out[f"{c}_{fn}"] = s if fn == "sum" else s / count[present]
    return pd.DataFrame(out)


def compare(got, want):
    """Keys, group order and counts exactly; sums and averages against their
    own size (every summed value is non-negative, so that is Σ|x|)."""
    n = 0 if got.rows == len(want) else max(got.rows, len(want))
    worst = 0.0
    if n == 0:
        for c in want.columns:
            if c in KEYS or c == "l_orderkey_count":
                n += bad(got[c], want[c].to_numpy())
            else:
                worst = max(worst, err(got[c], want[c].to_numpy(), np.abs(want[c].to_numpy())))
    return {"q1_err": worst if n == 0 else 1.0, "q1_bad": n}
