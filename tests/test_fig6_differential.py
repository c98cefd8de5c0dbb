"""Pandas-differential suite for the paper's Fig. 6 operators, through the
public ``DataFrame`` API.

Each case runs one operator on a seeded taxi-shaped frame of 2,000 rows
(``passenger_count`` small-range INT, ``payment_type`` coded, ``hack_license``
a wide-range INT, three float32-exact floats), registered on a row grid of
1, ``W`` or ``4W`` blocks (``W = schedule.pool_width()``), with no nulls or
with about 25% nulls in every column, and compares the answer with pandas on
the same data:

  * keys, counts, group order, labels, min, max, cummax, cummin, isna,
    dummies and transpose exactly;
  * float sums within 2e-5 of Σ|x| over the summed values (the benchmark's
    own limit), means within 2e-5 of Σ|x| / n.

The engine stores FLOAT as float32, so the inputs are float32-exact and
pandas computes on the same values in float64.  Null group keys are dropped,
as in pandas' ``groupby(dropna=True)``.  Any other place where the engine
differs from pandas on purpose is converted where the case is checked, with
the reason beside it.
"""
from __future__ import annotations

import math

import numpy as np
import pandas as pd
import pytest

from repro.core import EvalMode, Session, api, schedule

N_ROWS = 2000
FLOATS = ["trip_distance", "fare_amount", "tip_amount"]
NUMERIC = ["passenger_count", "hack_license", *FLOATS]
TOL = 2e-5


def _data(nulls: bool, seed: int = 17) -> dict:
    rng = np.random.default_rng(seed)
    n = N_ROWS
    # 40 drivers with license numbers spread over [0, 10**7): a key range
    # far past the dense-code cap, each license exact in float32
    licenses = rng.choice(10**7, 40, replace=False)
    cols = {
        "passenger_count": rng.integers(1, 7, n),
        "payment_type": rng.choice(["dispute", "card", "no_charge", "cash"],
                                   n),
        "hack_license": rng.choice(licenses, n),
        "trip_distance": rng.gamma(2.0, 1.5, n),
        "fare_amount": rng.normal(12.0, 8.0, n),
        "tip_amount": rng.normal(0.5, 2.0, n),
    }
    data = {}
    for name, vals in cols.items():
        if name in FLOATS:
            vals = vals.astype(np.float32)
        out = vals.tolist()
        if nulls:
            drop = rng.random(n) < 0.25
            out = [None if d else v for v, d in zip(out, drop)]
        data[name] = out
    return data


def _grid_params():
    w = schedule.pool_width()
    return [pytest.param(g, id=name) for name, g in
            (("grid1", 1), ("gridW", w), ("grid4W", 4 * w))]


def _canon(v):
    """One spelling of a null for exact comparison (None, NaN, pd.NA)."""
    if v is None or v is pd.NA or (isinstance(v, float) and math.isnan(v)):
        return None
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        return v.item()
    return v


def _col(values) -> list:
    return [_canon(v) for v in values]


def _run(build, grid: int, nulls: bool):
    """(engine Frame, pandas frame, ExecStats delta) for one statement built
    by ``build(df)`` in a fresh lazy session on a ``grid``-block frame."""
    data = _data(nulls)
    s = Session(mode=EvalMode.LAZY, default_row_parts=grid)
    try:
        before = {k: getattr(s.stats, k) for k in
                  ("groupby_dense", "groupby_factorized", "groupby_masked")}
        out = build(api.from_pydict(data, session=s)).collect()
        delta = {k: getattr(s.stats, k) - v for k, v in before.items()}
    finally:
        s.close()
    return out, pd.DataFrame(data), delta


def _assert_close(got: list, exp: list, scale: list, what: str) -> None:
    """``got`` equals ``exp`` within TOL × ``scale`` element by element,
    nulls in the same places."""
    assert len(got) == len(exp), what
    for i, (g, e, a) in enumerate(zip(got, _col(exp), _col(scale))):
        if e is None:
            assert g is None, f"{what}[{i}]: {g} vs null"
            continue
        assert g is not None, f"{what}[{i}]: null vs {e}"
        assert abs(g - e) <= TOL * a, f"{what}[{i}]: {g} vs {e} (Σ|x| {a})"


def _assert_groupby(got, exp: pd.DataFrame, close: dict) -> None:
    """``exp`` is pandas' result with the keys as columns (``reset_index``):
    same columns, group order and labels; the columns named in ``close``
    within TOL × their scale, every other column exact."""
    assert got.row_labels.to_list() == list(range(len(exp)))
    d = got.to_pydict()
    assert list(d) == list(exp.columns)
    for c in exp.columns:
        if c in close:
            _assert_close(_col(d[c]), exp[c], close[c], c)
        else:
            assert _col(d[c]) == _col(exp[c]), c


# =============================================================================
# the operators
# =============================================================================
def _isna(grid, nulls):
    got, pdf, _ = _run(lambda df: df.isna(), grid, nulls)
    exp = pdf.isna()
    d = got.to_pydict()
    assert got.row_labels.to_list() == list(range(N_ROWS))
    assert list(d) == list(exp.columns)
    for c in exp.columns:
        assert d[c] == exp[c].tolist(), c


def _count(grid, nulls):
    got, pdf, _ = _run(lambda df: df.count(), grid, nulls)
    d = got.to_pydict()
    # the engine's frame aggregates cover the numeric columns only
    assert list(d) == NUMERIC
    exp = pdf.count()
    for c in NUMERIC:
        assert d[c] == [int(exp[c])], c


def _frame_agg(grid, nulls):
    funcs = ["sum", "mean", "min", "max", "count"]
    got, pdf, _ = _run(lambda df: df.agg(funcs), grid, nulls)
    d = got.to_pydict()
    assert list(d) == NUMERIC
    # the engine's agg is a UNION of one-row GROUPBYs, one per function in
    # the listed order (paper §3.4), so its rows are told apart by position,
    # not by the function names pandas puts in the index
    assert got.nrows == len(funcs)
    exp = pdf[NUMERIC].astype("float64").agg(funcs)
    for c in NUMERIC:
        row = dict(zip(funcs, _col(d[c])))
        s_abs = float(pdf[c].abs().sum())
        n = int(exp.loc["count", c])
        _assert_close([row["sum"]], [exp.loc["sum", c]], [s_abs], f"{c} sum")
        _assert_close([row["mean"]], [exp.loc["mean", c]], [s_abs / n],
                      f"{c} mean")
        for f in ("min", "max", "count"):
            assert row[f] == exp.loc[f, c], (c, f)


def _groupby_coded_agg(grid, nulls):
    funcs = ["sum", "mean", "min", "max", "count"]
    got, pdf, delta = _run(
        lambda df: df.groupby("payment_type").agg({"fare_amount": funcs}),
        grid, nulls)
    assert delta["groupby_dense"] == 1
    g = pdf.groupby("payment_type")["fare_amount"]
    exp = g.agg(funcs).add_prefix("fare_amount_").reset_index()
    s_abs = g.apply(lambda x: x.abs().sum()).to_numpy()
    _assert_groupby(got, exp, {
        "fare_amount_sum": s_abs,
        "fare_amount_mean": s_abs / exp["fare_amount_count"].to_numpy()})


def _groupby_two_keys_count(grid, nulls):
    keys = ["payment_type", "passenger_count"]
    got, pdf, delta = _run(lambda df: df.groupby(keys).count(), grid, nulls)
    assert delta["groupby_dense"] == 1
    exp = pdf.groupby(keys).count().reset_index()
    exp["passenger_count"] = exp["passenger_count"].astype(int)
    _assert_groupby(got, exp, {})


def _groupby_wide_sum(grid, nulls):
    got, pdf, delta = _run(lambda df: df.groupby("hack_license").sum(),
                           grid, nulls)
    assert delta["groupby_factorized"] == 1
    vals = ["passenger_count", *FLOATS]
    g = pdf.groupby("hack_license")[vals]
    exp = g.sum().reset_index()
    exp["hack_license"] = exp["hack_license"].astype(int)
    s_abs = g.agg(lambda x: x.abs().sum())
    _assert_groupby(got, exp, {c: s_abs[c].to_numpy() for c in vals})


def _window(engine, pandas, scale=None):
    """A window case: ``engine`` on the float columns against ``pandas`` on
    the same columns, exact, or within TOL × ``scale`` row by row."""
    def case(grid, nulls):
        got, pdf, _ = _run(lambda df: engine(df[FLOATS]), grid, nulls)
        x = pdf[FLOATS]
        exp = pandas(x)
        assert got.row_labels.to_list() == list(range(N_ROWS))
        d = got.to_pydict()
        assert list(d) == FLOATS
        for c in FLOATS:
            if scale is None:
                assert _col(d[c]) == _col(exp[c]), c
            else:
                _assert_close(_col(d[c]), exp[c], scale(x)[c].fillna(0.0), c)
    return case


def _both(first, second):
    def case(grid, nulls):
        first(grid, nulls)
        second(grid, nulls)
    return case


def _get_dummies(grid, nulls):
    got, pdf, _ = _run(lambda df: api.get_dummies(df, ["payment_type"]),
                       grid, nulls)
    exp = pd.get_dummies(pdf, columns=["payment_type"], dtype=int)
    d = got.to_pydict()
    assert got.row_labels.to_list() == list(range(N_ROWS))
    # the engine puts the dummies where the source column stood, in its
    # dictionary order, where pandas appends them sorted: compare by name
    assert sorted(d) == sorted(exp.columns)
    src_null = pdf["payment_type"].isna()
    for c in exp.columns:
        want = exp[c]
        if c.startswith("payment_type_"):
            # the engine keeps the source's null mask on each dummy, where
            # pandas writes 0 for a null category
            want = want.astype(object).where(~src_null, None)
        assert _col(d[c]) == _col(want), c


def _transpose(grid, nulls):
    got, pdf, _ = _run(lambda df: df[FLOATS].T, grid, nulls)
    exp = pdf[FLOATS].T
    assert got.row_labels.to_list() == FLOATS
    d = got.to_pydict()
    assert list(d) == list(range(N_ROWS))
    for j in range(N_ROWS):
        assert _col(d[j]) == _col(exp[j]), j


def _q1_chain(grid, nulls):
    """filter → assign → groupby: the shape of TPC-H Q1, which the engine
    runs as a device keep-mask folded into the group codes."""
    keys = ["payment_type", "passenger_count"]

    def build(df):
        f = df[df["trip_distance"] <= 4.0]
        f["net"] = f["fare_amount"] * (f["tip_amount"] * -0.1 + 1.0)
        f["total"] = f["net"] + f["tip_amount"]
        return f.groupby(keys).agg({"net": ["sum", "mean"],
                                    "total": ["sum"],
                                    "fare_amount": ["count"]})

    got, pdf, delta = _run(build, grid, nulls)
    assert delta["groupby_masked"] == 1
    f = pdf[pdf["trip_distance"] <= 4.0].copy()
    f["net"] = f["fare_amount"] * (f["tip_amount"] * -0.1 + 1.0)
    f["total"] = f["net"] + f["tip_amount"]
    g = f.groupby(keys)
    exp = g.agg(net_sum=("net", "sum"), net_mean=("net", "mean"),
                total_sum=("total", "sum"),
                fare_amount_count=("fare_amount", "count")).reset_index()
    exp["passenger_count"] = exp["passenger_count"].astype(int)
    net_abs = g["net"].apply(lambda x: x.abs().sum()).to_numpy()
    _assert_groupby(got, exp, {
        "net_sum": net_abs,
        "net_mean": net_abs / g["net"].count().to_numpy(),
        "total_sum": g["total"].apply(lambda x: x.abs().sum()).to_numpy()})


# rolling(w) is a float32 prefix sum less the prefix sum w rows back
# (physical._rolling_combine): its error grows with the row number, past
# 2e-5 of the window's Σ|x| within 2,000 rows, and a null lag reads as 0, so
# the windows after a null are wrong
_ROLLING_DEFECT = pytest.mark.xfail(
    strict=True, reason="rolling_sum/rolling_mean: prefix-sum difference "
                        "loses precision and reads a null lag as 0")

_CASES = {
    "isna": _isna,
    "count": _count,
    "frame_agg": _frame_agg,
    "groupby_coded_agg": _groupby_coded_agg,
    "groupby_coded_int_count": _groupby_two_keys_count,
    "groupby_wide_int_sum": _groupby_wide_sum,
    "cumsum": _window(lambda f: f.cumsum(), lambda x: x.cumsum(),
                      lambda x: x.abs().cumsum()),
    "cummax": _window(lambda f: f.cummax(), lambda x: x.cummax()),
    "cummin": _window(lambda f: f.cummin(), lambda x: x.cummin()),
    "rolling": _both(
        _window(lambda f: f.rolling_sum(3), lambda x: x.rolling(3).sum(),
                lambda x: x.abs().rolling(3).sum()),
        _window(lambda f: f.rolling_mean(3), lambda x: x.rolling(3).mean(),
                lambda x: x.abs().rolling(3).sum() / 3)),
    "diff_shift": _both(
        _window(lambda f: f.diff(1), lambda x: x.diff(1),
                lambda x: x.abs() + x.abs().shift(1)),
        _window(lambda f: f.shift(1), lambda x: x.shift(1))),
    "get_dummies": _get_dummies,
    "transpose": _transpose,
    "q1_chain": _q1_chain,
}


def _op_params():
    out = []
    for name, fn in _CASES.items():
        marks = _ROLLING_DEFECT if name == "rolling" else ()
        for nulls, tag in ((False, "dense"), (True, "nulls")):
            out.append(pytest.param(fn, nulls, marks=marks,
                                    id=f"{name}-{tag}"))
    return out


@pytest.mark.parametrize("grid", _grid_params())
@pytest.mark.parametrize("case,nulls", _op_params())
def test_fig6_matches_pandas(case, nulls, grid):
    case(grid, nulls)
