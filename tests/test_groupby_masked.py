"""Masked producer fusion into GROUPBY.

A fused groupby whose chain is structured selections, maps that declare
what they read and set, projections and renames, over keys with a dense
range, runs its selections as a device keep-mask folded into the group
codes (``physical._masked_groupby``): no row take, nothing staged.  Its
answers must equal the staged fused path's and the unfused plan's exactly —
keys, group order, counts, labels and sums bit for bit — and
``ExecStats.groupby_masked`` says which FusedGroupBy nodes took it, in the
statement's stats and in the node span.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EvalMode, Session, api, physical
from repro.core import algebra as alg


def _table(s, n=3000, seed=3, nulls=False):
    rng = np.random.default_rng(seed)
    data = {
        "flag": rng.choice(["A", "N", "R"], n).tolist(),
        "status": rng.choice(["F", "O"], n).tolist(),
        "qty": rng.integers(1, 51, n).tolist(),
        "price": (rng.random(n) * 100 + 0.5).tolist(),
        "disc": (rng.integers(0, 11, n) / 100 + 0.001).tolist(),
        "tax": (rng.integers(0, 9, n) / 100 + 0.001).tolist(),
        "ship": rng.integers(0, 400, n).tolist(),
    }
    if nulls:
        for name, step in (("flag", 7), ("status", 11), ("price", 5),
                           ("qty", 13)):
            data[name] = [None if i % step == 0 else v
                          for i, v in enumerate(data[name])]
    return api.from_pydict(data, session=s)


def _q1(df, cut=300):
    f = df[df["ship"] <= cut]
    f["disc_price"] = f["price"] * (f["disc"] * -1.0 + 1.0)
    f["charge"] = f["disc_price"] * (f["tax"] + 1.0)
    return f.groupby(["flag", "status"]).agg({
        "qty": ["sum", "mean"], "price": ["sum", "mean"],
        "disc_price": ["sum"], "charge": ["sum", "min", "max"],
        "disc": ["mean", "std"], "ship": ["count"]})


def _q6(df, lo=100, hi=108):
    # about 2% of the rows: 8 of 400 ship days, then a discount band
    f = df[(df["ship"] >= lo) & (df["ship"] < hi)
           & (df["disc"] >= 0.02) & (df["disc"] <= 0.09)
           & (df["qty"] < 40)]
    f["revenue"] = f["price"] * f["disc"]
    return f["revenue"]


def _run(build, optimize=True, masked=True, nulls=False, parts=3):
    """(result, groupby_masked delta) of one statement in a fresh session;
    ``masked=False`` forces the staged fused path."""
    with pytest.MonkeyPatch.context() as m:
        if not masked:
            m.setattr(physical, "_masked_groupby", lambda *a: None)
        s = Session(mode=EvalMode.LAZY, optimize=optimize,
                    default_row_parts=parts)
        try:
            q = build(_table(s, nulls=nulls))
            st0 = dataclasses.replace(s.stats)
            out = q.sum() if isinstance(q, api.ColumnExpr) else q.collect()
            return out, s.stats.groupby_masked - st0.groupby_masked
        finally:
            s.close()


def _assert_same(a, b):
    if not hasattr(a, "to_pydict"):
        assert (a is None and b is None) or a == b, (a, b)
        return
    assert a.col_labels.to_list() == b.col_labels.to_list()
    assert a.row_labels.to_list() == b.row_labels.to_list()
    ad, bd = a.to_pydict(), b.to_pydict()
    for name in ad:
        av, bv = ad[name], bd[name]
        assert [x is None for x in av] == [x is None for x in bv], name
        assert a.col(name).domain is b.col(name).domain, name
        if av and isinstance(next((x for x in av if x is not None), 0), str):
            assert av == bv, name
            continue
        fa = np.asarray([0 if x is None else x for x in av])
        fb = np.asarray([0 if x is None else x for x in bv])
        np.testing.assert_array_equal(fa, fb, err_msg=str(name))


def _three_ways(build, **kw):
    masked, n_masked = _run(build, **kw)
    staged, n_staged = _run(build, masked=False, **kw)
    unfused, n_unfused = _run(build, optimize=False, **kw)
    assert (n_masked, n_staged, n_unfused) == (1, 0, 0)
    _assert_same(masked, unfused)
    _assert_same(masked, staged)
    return masked


CASES = {
    "q1_two_category_keys": (_q1, {}),
    "q6_no_keys_two_percent": (_q6, {}),
    "empty_selection_keys": (lambda df: _q1(df, cut=-1), {}),
    "empty_selection_no_keys": (lambda df: _q6(df, lo=500, hi=600), {}),
    "null_keys_and_values": (_q1, {"nulls": True}),
    "int_key": (lambda df: df[df["ship"] > 50].groupby("qty").agg(
        {"price": ["sum", "count"]}), {}),
    "empty_selection_int_key": (lambda df: df[df["ship"] < 0].groupby("qty").agg(
        {"price": ["sum", "count"]}), {}),
    "one_block": (_q1, {"parts": 1}),
    "coded_predicate": (lambda df: df[(df["flag"] == "A") & (df["ship"] < 200)]
                        .groupby("status").agg({"price": ["sum", "count"]}), {}),
    "rename_and_projection": (
        lambda df: df[df["ship"] < 200][["flag", "price", "ship"]]
        .rename(columns={"price": "p", "flag": "f"})
        .groupby("f").agg({"p": ["sum", "mean"]}), {}),
    # the projection drops the predicate's ``qty``, which comes before
    # ``price`` in the table, and the rename reuses its name
    "rename_onto_a_dropped_column": (
        lambda df: df[df["qty"] < 40][["flag", "price"]]
        .rename(columns={"price": "qty"})
        .groupby("flag").agg({"qty": ["sum"]}), {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_masked_path_equals_staged_and_unfused(case):
    build, kw = CASES[case]
    out = _three_ways(build, **kw)
    if case in ("empty_selection_keys", "empty_selection_int_key"):
        assert out.nrows == 0
    elif case == "empty_selection_no_keys":
        assert out is None            # one row, its sum null: nothing kept
    elif case == "q6_no_keys_two_percent":
        assert out > 0
    elif case == "null_keys_and_values":
        assert None not in out.to_pydict()["flag"]


def _reciprocal(df):
    # 1/x is inf where x == 0: only in rows the selection rejects
    f = df[df["x"] != 0.0]
    f["inv"] = (f["x"] * 0.0 + 1.0) / f["x"]
    return f.groupby("k").agg({"inv": ["sum", "mean", "min", "max"]})


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
def test_inf_and_nan_in_rejected_rows_reach_no_statistic(kernels, request):
    if kernels == "pallas":
        request.getfixturevalue("use_pallas_kernels")
    rng = np.random.default_rng(9)
    n = 600
    # powers of two: every reciprocal and every sum of them is exact
    x = rng.choice([0.0, 0.5, -1.0, 2.0, 4.0, -0.25], n).tolist()
    data = {"k": rng.integers(0, 4, n).tolist(), "x": x}

    def run(optimize, masked=True):
        with pytest.MonkeyPatch.context() as m:
            if not masked:
                m.setattr(physical, "_masked_groupby", lambda *a: None)
            s = Session(mode=EvalMode.LAZY, optimize=optimize,
                        default_row_parts=3)
            try:
                st0 = s.stats.groupby_masked
                out = _reciprocal(api.from_pydict(data, session=s)).collect()
                return out, s.stats.groupby_masked - st0
            finally:
                s.close()

    masked, n1 = run(True)
    staged, _ = run(True, masked=False)
    unfused, _ = run(False)
    assert n1 == 1
    _assert_same(masked, unfused)
    _assert_same(masked, staged)
    vals = np.asarray(masked.to_pydict()["inv_sum"], np.float64)
    assert np.isfinite(vals).all()


def _tiled(data, optimize=True, masked=True, place=None):
    """(result, groupby_masked delta) of a filtered sum over three blocks
    of 5,000 rows; ``place`` stands in for ``physical._kept_first``."""
    with pytest.MonkeyPatch.context() as m:
        if not masked:
            m.setattr(physical, "_masked_groupby", lambda *a: None)
        if place is not None:
            m.setattr(physical, "_kept_first", place)
        s = Session(mode=EvalMode.LAZY, optimize=optimize,
                    default_row_parts=3)
        try:
            st0 = s.stats.groupby_masked
            df = api.from_pydict(data, session=s)
            out = df[df["x"] < 50.0].groupby("k").agg(
                {"x": ["sum", "mean"], "y": ["sum"]}).collect()
            return out, s.stats.groupby_masked - st0
        finally:
            s.close()


def test_kept_rows_sum_in_the_unfused_tiles(use_pallas_kernels):
    # the Pallas partial sums each 2048-row tile on its own: blocks of
    # 5,000 rows span three tiles, the values do not sum exactly, and about
    # half of each tile is rejected, so the sums are bit-equal to the
    # unfused plan's only where each kept row sits where a compacted block
    # would hold it
    rng = np.random.default_rng(21)
    n = 15000
    data = {"k": rng.integers(0, 3, n).tolist(),
            "x": (rng.random(n) * 100).tolist(),
            "y": (rng.random(n) * 100 - 50).tolist()}
    masked, n_masked = _tiled(data)
    staged, _ = _tiled(data, masked=False)
    unfused, _ = _tiled(data, optimize=False)
    assert n_masked == 1
    _assert_same(masked, unfused)
    _assert_same(masked, staged)

    def in_place(keep, codes, datas, masks):
        return jnp.where(keep, codes, physical.NULL_CODE), datas, masks

    # the data tells the two placements apart: masking in place rounds
    # differently
    moved, _ = _tiled(data, place=in_place)
    with pytest.raises(AssertionError):
        _assert_same(moved, unfused)


def _udf_predicate(df):
    pred = alg.Udf(name="ship_over_100", elementwise=True,
                   deps=frozenset(["ship"]),
                   fn=lambda cols, frame: np.asarray(cols["ship"].data) > 100)
    f = df._derive(alg.Selection(df._node, pred))
    return f.groupby(["flag"]).agg({"price": ["sum"]})


def _computed_key(df):
    f = df[df["ship"] <= 300]
    f["qty"] = f["qty"] + 1
    return f.groupby("qty").agg({"price": ["sum"]})


def _new_key(df):
    f = df[df["ship"] <= 300]
    f["q2"] = f["qty"] * 2
    return f.groupby("q2").agg({"price": ["sum"]})


def _float_key(df):
    return df[df["ship"] <= 300].groupby("disc").agg({"price": ["sum"]})


def _rename_onto_a_kept_column(df):
    # two ``qty`` columns after the rename: the groupby reads the first
    f = df[df["qty"] < 40].rename(columns={"price": "qty"})
    return f.groupby("flag").agg({"qty": ["sum"]})


def _map_over_coded(df):
    f = df[df["ship"] <= 300]
    f["is_a"] = f["flag"] == "A"
    return f.groupby(["status"]).agg({"is_a": ["sum"], "price": ["sum"]})


INELIGIBLE = {
    "udf_predicate": _udf_predicate,
    "computed_key": _computed_key,
    "new_key_from_a_map": _new_key,
    "key_needs_factorization": _float_key,
    "map_over_coded_column": _map_over_coded,
    "rename_onto_a_kept_column": _rename_onto_a_kept_column,
}


@pytest.mark.parametrize("case", list(INELIGIBLE))
def test_ineligible_plans_keep_the_staged_path(case):
    build = INELIGIBLE[case]
    fused, n_fused = _run(build)
    unfused, _ = _run(build, optimize=False)
    assert n_fused == 0
    _assert_same(fused, unfused)


@pytest.mark.trace
@pytest.mark.parametrize("build", [_q1, _q6], ids=["q1", "q6"])
def test_masked_counter_lands_in_stats_and_node_span(build):
    s = Session(mode=EvalMode.LAZY, trace=True, default_row_parts=3)
    try:
        q = build(_table(s))
        st0 = dataclasses.replace(s.stats)
        q.sum() if isinstance(q, api.ColumnExpr) else q.collect()
        st1, tr = s.stats, s.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
        totals = tr.counter_totals(tr.last_stmt)
    finally:
        s.close()
    assert st1.groupby_masked - st0.groupby_masked == 1
    node = [sp for sp in spans if sp.name == "eval:fused_groupby"]
    assert len(node) == 1 and node[0].args["groupby_masked"] == 1
    assert totals["groupby_masked"] == 1
    assert not [sp for sp in spans if sp.name.startswith("stage:")]
    assert len([sp for sp in spans if sp.name == "groupby:stages"]) == 3


def test_traced_map_chain_runs_as_one_program(monkeypatch):
    # on an accelerator the map chain over the read columns traces as one
    # program (CPU defaults to eager); the assigns must trace, and the
    # answer stay the unfused plan's
    monkeypatch.setenv("REPRO_JIT_UDFS", "1")
    physical._MAP_JIT.clear()
    masked, n = _run(_q1)
    assert n == 1
    assert physical.map_jit_counts() == {"adopted": 1, "fell_back": 0}
    unfused, _ = _run(_q1, optimize=False)
    _assert_same(masked, unfused)


@pytest.mark.parametrize("expr, masked", [
    (alg.col("a") * (alg.col("b") + alg.lit(1.0)), False),
    (alg.col("i") // alg.col("j"), True),     # a zero divisor makes a null
])
def test_traced_assign_keeps_a_mask_only_where_a_null_can_arise(
        monkeypatch, expr, masked):
    # the eager assign drops an all-valid mask by reading it; the traced one
    # cannot read it, and decides from the expression and its columns
    monkeypatch.setenv("REPRO_JIT_UDFS", "1")
    physical._MAP_JIT.clear()
    s = Session(mode=EvalMode.LAZY)
    try:
        frame = api.from_pydict({"a": [0.5, 1.5, 2.5, 3.5],
                                 "b": [1.25, -2.5, 0.75, 4.5],
                                 "i": [7, 8, 9, 10], "j": [2, 0, 3, 4]},
                                session=s).collect()
    finally:
        s.close()
    udf = alg.Udf.wrap(api._expr_assign_fn("c", expr), name=f"assign_c_{expr!r}",
                       deps=frozenset(expr.refs()), writes=frozenset(["c"]))
    eager = physical._run_map_stages(frame, [udf])     # the probe
    assert physical.map_jit_counts() == {"adopted": 1, "fell_back": 0}
    traced = physical._run_map_stages(frame, [udf])
    assert (traced.col("c").mask is not None) == masked
    assert eager.col("c").to_pylist() == traced.col("c").to_pylist()
