"""Dense group codes against the general factorization.

A groupby whose keys are coded (dictionary) columns or small-range INT
columns takes mixed-radix dense codes (``physical._dense_keys``): no hashing,
no sort.  Its output must equal the general route's (``_factorize_keys``)
exactly in key values, group order, counts and row labels, and in sums and
means to float tolerance.  Keys that do not qualify must take the general
route, and ``ExecStats.groupby_dense`` / ``groupby_factorized`` say which
route each GROUPBY node took — in the statement's stats and in its node span.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import EvalMode, Session, api, physical, schedule
from repro.core.dtypes import Domain
from repro.core.executor import ExecStats
from repro.core.frame import Column, Frame
from repro.core.labels import RangeLabels, labels_from_values

AGGS = [("x", "sum", "xs"), ("x", "mean", "xm"), ("x", "count", "xc"),
        ("x", "min", "xmin")]


def _block(**cols) -> Frame:
    n = len(next(iter(cols.values())))
    return Frame(list(cols.values()), RangeLabels(n),
                 labels_from_values(list(cols)))


def _cat(values, dictionary=None, dom=Domain.CATEGORY) -> Column:
    """A coded column: ``values`` are strings (None = null) encoded against
    ``dictionary``, which may hold entries no row uses."""
    table = tuple(dictionary or dict.fromkeys(v for v in values if v is not None))
    codes = np.asarray([-1 if v is None else table.index(v) for v in values],
                       np.int32)
    mask = np.asarray([v is not None for v in values])
    return Column(jnp.asarray(codes), dom,
                  None if mask.all() else jnp.asarray(mask), table)


def _int(values, dtype=np.int32) -> Column:
    mask = np.asarray([v is not None for v in values])
    data = np.asarray([0 if v is None else v for v in values], dtype)
    return Column(jnp.asarray(data) if dtype == np.int32 else data, Domain.INT,
                  None if mask.all() else jnp.asarray(mask), None)


def _x(n, seed) -> Column:
    rng = np.random.default_rng(seed)
    return Column(jnp.asarray(rng.standard_normal(n).astype(np.float32)),
                  Domain.FLOAT)


def _rng_blocks(make_keys, sizes=(37, 50, 23), seed=0):
    rng = np.random.default_rng(seed)
    return [_block(**make_keys(rng, n), x=_x(n, seed + i))
            for i, n in enumerate(sizes)]


def _cat_cat(rng, n):
    return {"a": _cat(rng.choice(["R", "A", "N"], n).tolist()),
            "b": _cat(rng.choice(["O", "F"], n).tolist())}


def _cat_int(rng, n):
    return {"a": _cat(rng.choice(["b", "a", "c", "d"], n).tolist(),
                      dom=Domain.STR),
            "b": _int(rng.integers(-3, 4, n).tolist())}


def _int_int(rng, n):
    return {"a": _int(rng.integers(100, 120, n).tolist()),
            "b": _int(rng.integers(-5, 0, n).tolist())}


def _single_cat(rng, n):
    return {"a": _cat(rng.choice(["x", "y", "w", "z", "v"], n).tolist())}


def _nulls(rng, n):
    a = rng.choice(["p", "q", None], n).tolist()
    b = [None if v % 5 == 0 else int(v) for v in rng.integers(0, 9, n)]
    c = _cat(rng.choice(["m", "n"], n).tolist())
    # a null code with no mask must count as null too
    codes = np.asarray(c.data).copy()
    codes[::7] = -1
    return {"a": _cat(a), "b": _int(b),
            "c": Column(jnp.asarray(codes), Domain.CATEGORY, None, c.dictionary)}


def _unused_entries(rng, n):
    used = rng.choice(["k", "e"], n).tolist()
    return {"a": _cat(used, dictionary=("z", "k", "m", "e", "b")),
            "b": _int(rng.integers(0, 3, n).tolist())}


def _different_dicts(rng, n):
    # each block encodes its own first-occurrence dictionary
    pool = ["N", "A", "R", "F"]
    picks = list(rng.permutation(pool)[: 2 + n % 3])
    return {"a": _cat(rng.choice(picks, n).tolist()),
            "b": _cat(rng.choice(["O", "F"], n).tolist(),
                      dictionary=("F", "O") if n % 2 else ("O", "F"))}


DENSE_CASES = {
    "cat_x_cat": (_cat_cat, ("a", "b"), (37, 50, 23)),
    "cat_x_int": (_cat_int, ("a", "b"), (37, 50, 23)),
    "int_x_int": (_int_int, ("a", "b"), (37, 50, 23)),
    "single_cat": (_single_cat, ("a",), (37, 50, 23)),
    "nulls_in_each_key": (_nulls, ("a", "b", "c"), (41, 60, 29)),
    "different_dictionaries": (_different_dicts, ("a", "b"), (37, 50, 24, 31)),
    "unused_dictionary_entries": (_unused_entries, ("a", "b"), (37, 50)),
    "empty_block": (_cat_int, ("a", "b"), (37, 0, 23)),
}


def _run(blocks, keys, force_general=False, monkeypatch=None):
    st = ExecStats()
    if force_general:
        monkeypatch.setattr(physical, "_dense_keys", lambda *a: None)
    with schedule.stats_scope(st):
        out = physical._groupby_blocks(blocks, keys, AGGS).to_frame()
    return out, st


def _assert_same_groups(a: Frame, b: Frame, keys):
    assert a.col_labels.to_list() == b.col_labels.to_list()
    assert a.row_labels.to_list() == b.row_labels.to_list()
    ad, bd = a.to_pydict(), b.to_pydict()
    for k in keys:
        assert ad[k] == bd[k], k                     # values and group order
        assert a.col(k).domain is b.col(k).domain, k
    assert ad["xc"] == bd["xc"]                      # counts exact
    for name in ("xs", "xm", "xmin"):
        np.testing.assert_allclose(np.asarray(ad[name], np.float64),
                                   np.asarray(bd[name], np.float64),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("case", list(DENSE_CASES))
def test_dense_codes_match_general_factorization(case, monkeypatch):
    make, keys, sizes = DENSE_CASES[case]
    blocks = _rng_blocks(make, sizes)
    dense, st = _run(blocks, keys)
    assert (st.groupby_dense, st.groupby_factorized) == (1, 0)
    general, st_g = _run(blocks, keys, force_general=True,
                         monkeypatch=monkeypatch)
    assert (st_g.groupby_dense, st_g.groupby_factorized) == (0, 1)
    assert dense.nrows > 0
    _assert_same_groups(dense, general, keys)


def _wide_product(rng, n):
    return {"a": _int((rng.integers(0, 2, n) * 299).tolist()),
            "b": _int((rng.integers(0, 2, n) * 299).tolist())}


def _wide_range(rng, n):
    return {"a": _int((rng.integers(0, 2, n) * 100_000).tolist())}


def _wide_int64(rng, n):
    return {"a": _int((rng.integers(-1, 2, n) * 2 ** 60).tolist(), np.int64)}


def _float_key(rng, n):
    return {"a": Column(jnp.asarray(rng.choice([0.5, 1.5], n)
                                    .astype(np.float32)), Domain.FLOAT)}


FALLBACK_CASES = {
    "product_over_cap": (_wide_product, ("a", "b")),   # 300 × 300 slots
    "int_range_over_cap": (_wide_range, ("a",)),
    "wide_int64_key": (_wide_int64, ("a",)),
    "float_key": (_float_key, ("a",)),
}


@pytest.mark.parametrize("case", list(FALLBACK_CASES))
def test_keys_without_dense_range_take_general_factorization(case, monkeypatch):
    make, keys = FALLBACK_CASES[case]
    blocks = _rng_blocks(make)
    calls = []
    real = physical._factorize_keys
    monkeypatch.setattr(physical, "_factorize_keys",
                        lambda *a: calls.append(1) or real(*a))
    out, st = _run(blocks, keys)
    assert calls == [1]
    assert (st.groupby_dense, st.groupby_factorized) == (0, 1)
    assert sum(out.to_pydict()["xc"]) == sum(b.nrows for b in blocks)


def _q1_like(s, n=2000, seed=5):
    rng = np.random.default_rng(seed)
    df = api.from_pydict({
        "flag": rng.choice(["A", "N", "R"], n).tolist(),
        "status": rng.choice(["F", "O"], n).tolist(),
        "qty": rng.integers(1, 51, n).tolist(),
        "price": (rng.random(n) * 100 + 0.5).tolist(),
        "ship": rng.integers(0, 200, n).tolist()}, session=s)
    return df[df["ship"] <= 150]


QUERIES = {
    "two_category_keys": lambda f: f.groupby(["flag", "status"]).agg(
        {"price": ["sum", "mean"]}),
    "single_int_key": lambda f: f.groupby("qty").agg({"price": ["sum"]}),
    "float_key": lambda f: f.groupby("price").agg({"qty": ["count"]}),
}
ROUTE = {"two_category_keys": (1, 0), "single_int_key": (1, 0),
         "float_key": (0, 1)}


@pytest.mark.trace
@pytest.mark.parametrize("optimize", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("query", list(QUERIES))
def test_route_counters_land_in_stats_and_node_span(query, optimize):
    s = Session(mode=EvalMode.LAZY, trace=True, optimize=optimize,
                default_row_parts=3)
    try:
        q = QUERIES[query](_q1_like(s))
        st0 = dataclasses.replace(s.stats)
        q.collect()
        st1, tr = s.stats, s.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
    finally:
        s.close()
    moved = (st1.groupby_dense - st0.groupby_dense,
             st1.groupby_factorized - st0.groupby_factorized)
    assert moved == ROUTE[query]
    op = "eval:fused_groupby" if optimize else "eval:groupby"
    node = [sp for sp in spans if sp.name == op]
    assert len(node) == 1
    assert (node[0].args["groupby_dense"],
            node[0].args["groupby_factorized"]) == ROUTE[query]
    totals = tr.counter_totals(tr.last_stmt)
    assert (totals["groupby_dense"], totals["groupby_factorized"]) == moved
