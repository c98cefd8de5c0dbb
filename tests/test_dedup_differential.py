"""Pandas-differential property suite for DIFFERENCE / DROP-DUPLICATES — the
gate for the block-parallel + barrier-fused paths (PR 4).

Properties asserted for every generated case:

  * **pandas oracle** — results are value- and index-identical to pandas
    (``drop_duplicates`` directly; a pandas-mediated full-row anti-join for
    DIFFERENCE, which pandas does not expose as one call);
  * **grid invariance** — identical across partition grids of 1, ``workers``
    and ``4 × workers`` row blocks;
  * **plan invariance** — identical between fused (``optimize=True``) and
    per-node (``optimize=False``) plans, and between the block-parallel path
    and the serial seed path (``REPRO_BLOCK_DEDUP=0``).

Cases mix int / float / coded columns, null masks, duplicate-heavy and
duplicate-free distributions, and the 0-row / 0-col edges.  Floats are
float32-exact so value equality against the oracle is bitwise.

Runs property-based through hypothesis when it is installed; the seeded
parametrized sweep below covers the same generator deterministically either
way, so this gate never goes vacuous on a container without dev extras.
"""
from __future__ import annotations

import numpy as np
import pandas as pd
import pytest

from repro.core import algebra as alg
from repro.core import schedule
from repro.core.dtypes import Domain
from repro.core.executor import Executor
from repro.core.frame import Column, Frame
from repro.core.labels import RangeLabels, labels_from_values
from repro.core.partition import PartitionedFrame

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# =============================================================================
# case generation (shared by the seeded sweep and the hypothesis properties)
# =============================================================================
_STRINGS = ["aa", "bb", "cc", "dd", "ee", "ff", "gg", "hh"]
_FLOATS = [float(np.float32(x)) for x in
           (0.5, -1.25, 3.75, 7.125, -0.625, 2.5, 9.875, -4.5)]


def _gen_column(rng: np.random.Generator, kind: str, nrows: int,
                pool: int, null_p: float) -> list:
    """One host column: values drawn from a ``pool``-sized alphabet (small
    pool ⇒ duplicate-heavy, large ⇒ mostly duplicate-free), nulls injected
    with probability ``null_p``."""
    if kind == "int":
        vals = rng.integers(0, max(pool, 1), nrows).tolist()
    elif kind == "float":
        vals = [_FLOATS[i % len(_FLOATS)]
                for i in rng.integers(0, max(pool, 1), nrows)]
    else:  # coded
        vals = [_STRINGS[i % len(_STRINGS)]
                for i in rng.integers(0, max(pool, 1), nrows)]
    if null_p > 0:
        nulls = rng.random(nrows) < null_p
        vals = [None if n else v for v, n in zip(vals, nulls)]
    return vals


_KINDS = ("int", "float", "coded")
_DOMS = {"int": Domain.INT, "float": Domain.FLOAT, "coded": Domain.STR}


def _gen_case(seed: int, *, dup_heavy: bool | None = None,
              nrows: int | None = None) -> tuple[dict, list]:
    """(data dict, domains) for one random frame."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 60)) if nrows is None else nrows
    ncols = int(rng.integers(1, 5))
    heavy = bool(rng.integers(0, 2)) if dup_heavy is None else dup_heavy
    pool = 3 if heavy else 50
    null_p = float(rng.choice([0.0, 0.15, 0.4]))
    data, domains = {}, []
    for j in range(ncols):
        kind = _KINDS[int(rng.integers(0, len(_KINDS)))]
        data[f"c{j}_{kind}"] = _gen_column(rng, kind, n, pool, null_p)
        domains.append(_DOMS[kind])
    return data, domains


def _grids() -> tuple[int, ...]:
    w = schedule.pool_width()
    return (1, w, 4 * w)


# =============================================================================
# oracles (pandas-mediated) and result comparison
# =============================================================================
def _to_pandas(data: dict) -> pd.DataFrame:
    # object dtype: no int→float coercion under nulls, None stays None, and
    # drop_duplicates hashes the exact python values our frames round-trip
    if not data:
        return pd.DataFrame()
    return pd.DataFrame({k: pd.Series(v, dtype=object)
                         for k, v in data.items()})


def _pd_lists(pdf: pd.DataFrame) -> tuple[list, dict]:
    return list(pdf.index), {c: list(pdf[c]) for c in pdf.columns}


def _frame_lists(f: Frame) -> tuple[list, dict]:
    return f.row_labels.to_list(), f.to_pydict()


def _oracle_dedup(data: dict, subset) -> tuple[list, dict]:
    pdf = _to_pandas(data)
    out = pdf.drop_duplicates(subset=list(subset)) if subset else (
        pdf.drop_duplicates())
    return _pd_lists(out)


def _oracle_difference(ldata: dict, rdata: dict) -> tuple[list, dict]:
    """Full-row anti-join through pandas: left rows whose value tuple appears
    in the right input are dropped (null == null, as in pandas ``isin`` /
    ``duplicated`` hashing); survivors keep left order and index."""
    lp, rp = _to_pandas(ldata), _to_pandas(rdata)
    rset = set(rp.itertuples(index=False, name=None))
    keep = [t not in rset for t in lp.itertuples(index=False, name=None)]
    if lp.shape[1] == 0:
        keep = [len(rp) == 0] * len(lp)
    return _pd_lists(lp[np.asarray(keep, dtype=bool)] if len(lp) else lp)


def _assert_result(got: Frame, expected: tuple[list, dict], ctx: str) -> None:
    gi, gc = _frame_lists(got)
    ei, ec = expected
    assert gi == ei, f"{ctx}: row labels {gi} != {ei}"
    assert list(gc) == list(ec), f"{ctx}: columns {list(gc)} != {list(ec)}"
    for name in ec:
        assert gc[name] == ec[name], f"{ctx}/{name}: {gc[name]} != {ec[name]}"


def _sweep(plan_of, frames: dict[str, Frame], expected, ctx: str,
           monkeypatch=None) -> None:
    """Evaluate ``plan_of()`` against the oracle across partition grids ×
    fused/unfused plans (× the serial seed path when ``monkeypatch`` is
    given) — the full invariance matrix of the suite docstring."""
    for rp in _grids():
        store = {fid: PartitionedFrame.from_frame(f, row_parts=rp)
                 for fid, f in frames.items()}
        for optimize in (True, False):
            got = Executor(store, optimize=optimize).evaluate(plan_of()).to_frame()
            _assert_result(got, expected, f"{ctx}[grid={rp},opt={optimize}]")
        if monkeypatch is not None:
            monkeypatch.setenv("REPRO_BLOCK_DEDUP", "0")
            try:
                got = Executor(store).evaluate(plan_of()).to_frame()
            finally:
                monkeypatch.delenv("REPRO_BLOCK_DEDUP")
            _assert_result(got, expected, f"{ctx}[grid={rp},serial]")


# =============================================================================
# the property cores
# =============================================================================
def _check_dedup(seed: int, monkeypatch=None, subset_from_seed: bool = False,
                 **gen_kw) -> None:
    data, domains = _gen_case(seed, **gen_kw)
    subset = None
    if subset_from_seed:
        names = list(data)
        k = 1 + seed % len(names)
        subset = tuple(names[:k])
    expected = _oracle_dedup(data, subset)
    f = Frame.from_pydict(data, domains=domains)
    plan = lambda: alg.DropDuplicates(alg.Source("src"),
                                      list(subset) if subset else None)
    _sweep(plan, {"src": f}, expected, f"dedup[seed={seed},subset={subset}]",
           monkeypatch)


def _check_difference(seed: int, monkeypatch=None) -> None:
    # both sides drawn duplicate-heavy from the same pools so overlap is real
    ldata, ldom = _gen_case(seed, dup_heavy=True)
    rng = np.random.default_rng(seed + 10_000)
    n_r = int(rng.integers(0, 40))
    rdata = {}
    for name, vals in ldata.items():
        kind = name.split("_")[-1]
        rdata[name] = _gen_column(rng, kind, n_r, 3,
                                  0.3 if any(v is None for v in vals) else 0.0)
    expected = _oracle_difference(ldata, rdata)
    lf = Frame.from_pydict(ldata, domains=ldom)
    rf = Frame.from_pydict(rdata, domains=ldom)
    if rf.nrows == 0:   # PartitionedFrame requires ≥1 (possibly 0-row) block
        rf = Frame([Column(np.zeros(0, dtype=np.float32), d) for d in ldom],
                   RangeLabels(0), labels_from_values(list(ldata)))
    plan = lambda: alg.Difference(alg.Source("l"), alg.Source("r"))
    _sweep(plan, {"l": lf, "r": rf}, expected, f"difference[seed={seed}]",
           monkeypatch)


# =============================================================================
# seeded deterministic sweep (the always-on gate)
# =============================================================================
@pytest.mark.parametrize("seed", range(10))
def test_dedup_matches_pandas(seed, monkeypatch):
    _check_dedup(seed, monkeypatch)


# 327117746: hypothesis-found 0-row right side whose coded column carries
# float storage (codes must be cast to int before indexing the value hash)
@pytest.mark.parametrize("seed", [*range(10), 327117746])
def test_difference_matches_pandas(seed, monkeypatch):
    _check_difference(seed, monkeypatch)


@pytest.mark.parametrize("seed", range(6))
def test_dedup_subset_matches_pandas(seed, monkeypatch):
    _check_dedup(seed + 100, monkeypatch, subset_from_seed=True)


@pytest.mark.parametrize("seed", (3, 17))
def test_dedup_duplicate_free(seed, monkeypatch):
    _check_dedup(seed, monkeypatch, dup_heavy=False, nrows=40)


@pytest.mark.parametrize("seed", (5, 23))
def test_dedup_duplicate_heavy(seed, monkeypatch):
    _check_dedup(seed, monkeypatch, dup_heavy=True, nrows=50)


# ---- hypothesis: the same properties, adversarially driven ------------------
if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_hypothesis_dedup_matches_pandas(seed):
        _check_dedup(seed)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_hypothesis_difference_matches_pandas(seed):
        _check_difference(seed)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_hypothesis_dedup_subset(seed):
        _check_dedup(seed, subset_from_seed=True)


# =============================================================================
# edges: 0-row / 0-col
# =============================================================================
def _empty_cols_frame(nrows: int) -> Frame:
    return Frame([], RangeLabels(nrows), labels_from_values([]))


def test_dedup_zero_rows(monkeypatch):
    data = {"k": [], "x": []}
    f = Frame.from_pydict(data, domains=[Domain.INT, Domain.FLOAT])
    _sweep(lambda: alg.DropDuplicates(alg.Source("src"), None), {"src": f},
           _oracle_dedup(data, None), "dedup-0row", monkeypatch)


def test_dedup_zero_cols(monkeypatch):
    # pandas keeps EVERY row of a column-less frame (nothing to compare)
    f = _empty_cols_frame(4)
    expected = _pd_lists(pd.DataFrame(index=range(4)).drop_duplicates())
    assert expected[0] == [0, 1, 2, 3]
    _sweep(lambda: alg.DropDuplicates(alg.Source("src"), None), {"src": f},
           expected, "dedup-0col", monkeypatch)


def test_difference_zero_rows_left(monkeypatch):
    z = Frame.from_pydict({"k": [], "x": []}, domains=[Domain.INT, Domain.FLOAT])
    r = Frame.from_pydict({"k": [1], "x": [0.5]}, domains=[Domain.INT, Domain.FLOAT])
    _sweep(lambda: alg.Difference(alg.Source("l"), alg.Source("r")),
           {"l": z, "r": r},
           _oracle_difference({"k": [], "x": []}, {"k": [1], "x": [0.5]}),
           "diff-0row-left", monkeypatch)


def test_difference_empty_right_keeps_left(monkeypatch):
    ldata = {"k": [1, 2, 2], "x": [0.5, 1.5, 1.5]}
    l = Frame.from_pydict(ldata, domains=[Domain.INT, Domain.FLOAT])
    r = Frame.from_pydict({"k": [], "x": []}, domains=[Domain.INT, Domain.FLOAT])
    _sweep(lambda: alg.Difference(alg.Source("l"), alg.Source("r")),
           {"l": l, "r": r}, _oracle_difference(ldata, {"k": [], "x": []}),
           "diff-empty-right", monkeypatch)


def test_difference_zero_cols():
    # no attributes ⇒ every left row matches the (empty) right tuple
    store = {"l": PartitionedFrame.from_frame(_empty_cols_frame(3)),
             "r": PartitionedFrame.from_frame(_empty_cols_frame(2))}
    out = Executor(store).evaluate(
        alg.Difference(alg.Source("l"), alg.Source("r"))).to_frame()
    assert out.shape == (0, 0)


# =============================================================================
# null-key semantics (null == null, like pandas hashing)
# =============================================================================
def test_dedup_null_keys(monkeypatch):
    data = {"k": [None, 1, None, 1, None], "s": ["aa", None, "aa", None, "bb"]}
    f = Frame.from_pydict(data, domains=[Domain.INT, Domain.STR])
    _sweep(lambda: alg.DropDuplicates(alg.Source("src"), None), {"src": f},
           _oracle_dedup(data, None), "dedup-nulls", monkeypatch)


def test_difference_null_keys(monkeypatch):
    ldata = {"k": [None, 1, 2], "x": [0.5, None, 1.5]}
    rdata = {"k": [None, 2], "x": [0.5, 1.5]}
    l = Frame.from_pydict(ldata, domains=[Domain.INT, Domain.FLOAT])
    r = Frame.from_pydict(rdata, domains=[Domain.INT, Domain.FLOAT])
    _sweep(lambda: alg.Difference(alg.Source("l"), alg.Source("r")),
           {"l": l, "r": r}, _oracle_difference(ldata, rdata),
           "diff-nulls", monkeypatch)


# =============================================================================
# coded columns: cross-dictionary equality + subset naming a coded column
# =============================================================================
def test_difference_cross_dictionary_coded(monkeypatch):
    """Same string values, different dictionary orders on the two inputs:
    equality must hold value-wise, not code-wise."""
    ldata = {"s": ["aa", "bb", "cc", "bb"], "k": [1, 2, 3, 2]}
    rdata = {"s": ["cc", "bb"], "k": [3, 2]}   # first-occurrence order differs
    l = Frame.from_pydict(ldata, domains=[Domain.STR, Domain.INT])
    r = Frame.from_pydict(rdata, domains=[Domain.STR, Domain.INT])
    assert l.col("s").dictionary != r.col("s").dictionary
    _sweep(lambda: alg.Difference(alg.Source("l"), alg.Source("r")),
           {"l": l, "r": r}, _oracle_difference(ldata, rdata),
           "diff-crossdict", monkeypatch)


def test_difference_cross_dictionary_disjoint_values(monkeypatch):
    ldata = {"s": ["aa", "bb", "aa"]}
    rdata = {"s": ["zz", "bb"]}    # partially disjoint tables
    l = Frame.from_pydict(ldata, domains=[Domain.STR])
    r = Frame.from_pydict(rdata, domains=[Domain.STR])
    _sweep(lambda: alg.Difference(alg.Source("l"), alg.Source("r")),
           {"l": l, "r": r}, _oracle_difference(ldata, rdata),
           "diff-disjointdict", monkeypatch)


def test_dedup_subset_coded_column(monkeypatch):
    data = {"s": ["aa", "bb", "aa", None, "bb", None],
            "x": [0.5, 1.5, 2.5, 3.5, 4.5, 5.5]}
    f = Frame.from_pydict(data, domains=[Domain.STR, Domain.FLOAT])
    _sweep(lambda: alg.DropDuplicates(alg.Source("src"), ["s"]), {"src": f},
           _oracle_dedup(data, ("s",)), "dedup-subset-coded", monkeypatch)


# =============================================================================
# int64 → float64 precision regression (keys 2**53 and 2**53 + 1)
# =============================================================================
def _wide_frame(values: list, extra: dict | None = None) -> Frame:
    cols = [Column(np.asarray(values, dtype=np.int64), Domain.INT)]
    names = ["k"]
    for n, (vals, dom) in (extra or {}).items():
        cols.append(Column(np.asarray(vals), dom))
        names.append(n)
    return Frame(cols, RangeLabels(len(values)), labels_from_values(names))


def test_wide_int_dedup_distinguishes_above_2_53(monkeypatch):
    f = _wide_frame([2**53, 2**53 + 1, 2**53, 2**53 + 1])
    for rp in _grids():
        store = {"src": PartitionedFrame.from_frame(f, row_parts=rp)}
        out = Executor(store).evaluate(
            alg.DropDuplicates(alg.Source("src"), None)).to_frame()
        assert out.col("k").to_pylist() == [2**53, 2**53 + 1], rp
        monkeypatch.setenv("REPRO_BLOCK_DEDUP", "0")
        try:
            ser = Executor(store).evaluate(
                alg.DropDuplicates(alg.Source("src"), None)).to_frame()
        finally:
            monkeypatch.delenv("REPRO_BLOCK_DEDUP")
        assert ser.col("k").to_pylist() == [2**53, 2**53 + 1], rp


def test_wide_int_difference_narrow_other_side():
    # the RIGHT side alone wouldn't flag the column wide — the joint decision
    # across both inputs (and across blocks) must still hash consistently
    l = _wide_frame([2**53, 2**53 + 1, 5])
    r = _wide_frame([2**53, 5])
    for rp in _grids():
        store = {"l": PartitionedFrame.from_frame(l, row_parts=rp),
                 "r": PartitionedFrame.from_frame(r, row_parts=1)}
        out = Executor(store).evaluate(
            alg.Difference(alg.Source("l"), alg.Source("r"))).to_frame()
        assert out.col("k").to_pylist() == [2**53 + 1], rp


def test_wide_int_join_no_false_match():
    l = _wide_frame([2**53, 2**53 + 1],
                    extra={"x": ([1.0, 2.0], Domain.FLOAT)})
    r = _wide_frame([2**53], extra={"y": ([9.0], Domain.FLOAT)})
    store = {"l": PartitionedFrame.from_frame(l),
             "r": PartitionedFrame.from_frame(r)}
    out = Executor(store).evaluate(
        alg.Join(alg.Source("l"), alg.Source("r"), on=["k"],
                 how="inner")).to_frame()
    assert out.col("k").to_pylist() == [2**53]
    assert out.col("x").to_pylist() == [1.0]


def test_wide_int_against_float_column_keeps_fractional_distinct():
    """A wide-flagged position shared with a FLOAT column must not truncate
    the floats: 1.5 on the right equals NOTHING on an integer left, while an
    integral 5.0 still equals int 5."""
    l = _wide_frame([1, 5, 2**53 + 1])
    r = Frame([Column(np.asarray([1.5, 5.0], dtype=np.float32), Domain.FLOAT)],
              RangeLabels(2), labels_from_values(["k"]))
    for rp in (1, 2, 3):
        store = {"l": PartitionedFrame.from_frame(l, row_parts=rp),
                 "r": PartitionedFrame.from_frame(r, row_parts=1)}
        out = Executor(store).evaluate(
            alg.Difference(alg.Source("l"), alg.Source("r"))).to_frame()
        # 5 == 5.0 drops; 1 != 1.5 and 2**53+1 survive
        assert out.col("k").to_pylist() == [1, 2**53 + 1], rp


def test_wide_int_join_against_float_no_truncated_match():
    l = _wide_frame([1, 2**53 + 1], extra={"x": ([1.0, 2.0], Domain.FLOAT)})
    r = Frame([Column(np.asarray([1.5], dtype=np.float32), Domain.FLOAT),
               Column(np.asarray([9.0], dtype=np.float32), Domain.FLOAT)],
              RangeLabels(1), labels_from_values(["k", "y"]))
    store = {"l": PartitionedFrame.from_frame(l),
             "r": PartitionedFrame.from_frame(r)}
    out = Executor(store).evaluate(
        alg.Join(alg.Source("l"), alg.Source("r"), on=["k"],
                 how="inner")).to_frame()
    assert out.nrows == 0    # 1 != 1.5 — an int64 cast would have matched


def test_wide_int_column_selection_exact():
    """Directly-constructed int64 host columns compare exactly in selections
    — both the interpreted path and the fused predicate-chain path (which
    must refuse the jit boundary: a jax literal/trace would truncate them
    through int32).  Ingest stays LOUD: `parse_column` refuses beyond-int32
    integers rather than storing something device paths would corrupt."""
    from repro.core.dtypes import parse_column
    with pytest.raises(OverflowError):
        parse_column([2**53, 2**53 + 1, 7])
    f = _wide_frame([2**53, 2**53 + 1, 7])
    store = {"s": PartitionedFrame.from_frame(f)}
    out = Executor(store).evaluate(
        alg.Selection(alg.Source("s"), alg.col("k") > alg.lit(8))).to_frame()
    assert out.col("k").to_pylist() == [2**53, 2**53 + 1]
    chain = alg.Selection(alg.Selection(alg.Source("s"),
                                        alg.col("k") > alg.lit(8)),
                          alg.col("k") < alg.lit(2**53 + 1))
    out2 = Executor(store, optimize=True).evaluate(chain).to_frame()
    assert out2.col("k").to_pylist() == [2**53]


def test_wide_int_binops_numpy_semantics():
    """Predicates over a wide int64 host column follow numpy semantics: the
    pair is pinned to host numpy (mixed np/jax ops would canonicalize the
    wide side through int32), including %, //, comparisons against int32
    device columns and against float literals."""
    import jax.numpy as jnp
    vals = np.asarray([2**40 + 3, 2**40 + 4, 7], dtype=np.int64)
    # n holds exactly the int32 truncation artifacts of k's wide values: a
    # truncating comparison would "equal" every row, the exact path none
    f = Frame([Column(vals, Domain.INT),
               Column(jnp.asarray([3, 4, 7], dtype=jnp.int32), Domain.INT)],
              RangeLabels(3), labels_from_values(["k", "n"]))
    store = {"g": PartitionedFrame.from_frame(f)}

    def sel(pred):
        return Executor(store).evaluate(
            alg.Selection(alg.Source("g"), pred)).to_frame().col("k").to_pylist()

    assert sel((alg.col("k") % alg.lit(10)) == alg.lit(9)) == \
        vals[(vals % 10) == 9].tolist()
    assert sel((alg.col("k") // alg.lit(2**20)) == alg.lit(2**20)) == \
        vals[(vals // 2**20) == 2**20].tolist()
    # wide vs int32 device column: 2**40+3 == 3 must NOT match (truncation)
    assert sel(alg.col("k") == alg.col("n")) == [7]
    # wide vs fractional float literal: numpy promotion (float64)
    ref = vals > np.float32(2**40 + 3.5)
    assert sel(alg.col("k") > alg.lit(float(2**40 + 3.5))) == vals[ref].tolist()
    # zero divisors null out, with no host-path warnings/crashes
    assert sel((alg.col("k") % alg.lit(0)).notna()) == []


def test_factorization_tasks_not_counted_as_row_blocks():
    """Per-column factorization pool tasks must not pollute the row-block
    scheduling counters (`dispatched_blocks` attributes coalescing)."""
    f = Frame.from_pydict({"k": [1, 2, 1, 2], "v": [1.5, 2.5, 1.5, 2.5],
                           "s": ["ax", "bx", "ax", "bx"]})
    store = {"s": PartitionedFrame.from_frame(f, row_parts=2)}
    ex = Executor(store)
    ex.evaluate(alg.DropDuplicates(alg.Source("s"), None))
    assert ex.stats.dispatched_blocks == 4   # 2 key blocks + 2 filter blocks
    assert ex.stats.dedup_blocks == 2


def test_wide_int_groupby_distinct_groups():
    f = _wide_frame([0, 2**53, 2**53 + 1, 2**53],
                    extra={"v": ([1.0, 2.0, 3.0, 4.0], Domain.FLOAT)})
    store = {"g": PartitionedFrame.from_frame(f, row_parts=2)}
    out = Executor(store).evaluate(
        alg.GroupBy(alg.Source("g"), ("k",), [("v", "sum", "vs")])).to_frame()
    assert out.col("k").to_pylist() == [0, 2**53, 2**53 + 1]
    assert out.col("vs").to_pylist() == [1.0, 6.0, 3.0]


# =============================================================================
# fused ≡ unfused through producer/consumer chains (+ counters)
# =============================================================================
def _scale_udf(name: str = "x") -> alg.Udf:
    def fn(cols, frame):
        out = dict(cols)
        c = cols[name]
        # ×2 is exact in float32 AND float64 → the pandas mirror is trivial
        out[name] = Column(c.data * np.float32(2.0), Domain.FLOAT, c.mask, None)
        return out
    return alg.Udf(name=f"dedup_diff_scale_{name}", fn=fn,
                   deps=frozenset([name]), elementwise=True)


def _chain_case(seed: int) -> tuple[dict, list]:
    rng = np.random.default_rng(seed)
    n = 40
    return {
        "k": _gen_column(rng, "int", n, 3, 0.1),
        "x": _gen_column(rng, "float", n, 3, 0.1),
        "s": _gen_column(rng, "coded", n, 3, 0.1),
    }, [Domain.INT, Domain.FLOAT, Domain.STR]


def _pd_chain_dedup(data: dict) -> tuple[list, dict]:
    """pandas mirror of map(x*2) → filter(k>0) → drop_duplicates."""
    mapped = dict(data, x=[None if v is None else v * 2 for v in data["x"]])
    pdf = _to_pandas(mapped)   # object dtype: mapped Nones stay None, not NaN
    keep = [v is not None and v > 0 for v in pdf["k"]]
    return _pd_lists(pdf[np.asarray(keep, dtype=bool)].drop_duplicates())


@pytest.mark.parametrize("seed", (1, 9))
def test_fused_producer_chain_dedup(seed, monkeypatch):
    data, domains = _chain_case(seed)
    expected = _pd_chain_dedup(data)
    f = Frame.from_pydict(data, domains=domains)
    plan = lambda: alg.DropDuplicates(
        alg.Selection(alg.Map(alg.Source("src"), _scale_udf()),
                      alg.col("k") > alg.lit(0)), None)
    _sweep(plan, {"src": f}, expected, f"fused-chain-dedup[{seed}]",
           monkeypatch)
    # plan shape: the chain was absorbed as producer stages
    store = {"src": PartitionedFrame.from_frame(f, row_parts=4)}
    ex = Executor(store, optimize=True)
    prepared = ex._prepared(plan())
    assert prepared.op == "fused_drop_duplicates"
    assert len(prepared.params["pre_stages"]) == 2
    assert prepared.params["grid"] == "workers"
    ex.evaluate(plan())
    assert ex.stats.barrier_fused_groups == 1
    assert ex.stats.producer_stage_ops == 2
    assert ex.stats.dedup_blocks > 0 and ex.stats.dedup_key_rows > 0


def test_fused_producer_chains_difference_both_sides(monkeypatch):
    ldata, ldom = _chain_case(2)
    rdata, _ = _chain_case(3)
    lf = Frame.from_pydict(ldata, domains=ldom)
    rf = Frame.from_pydict(rdata, domains=ldom)
    plan = lambda: alg.Difference(
        alg.Map(alg.Source("l"), _scale_udf()),
        alg.Map(alg.Source("r"), _scale_udf()))

    def mapped(d):
        return dict(d, x=[None if v is None else v * 2 for v in d["x"]])

    expected = _oracle_difference(mapped(ldata), mapped(rdata))
    _sweep(plan, {"l": lf, "r": rf}, expected, "fused-diff-both",
           monkeypatch)
    store = {"l": PartitionedFrame.from_frame(lf, row_parts=4),
             "r": PartitionedFrame.from_frame(rf, row_parts=4)}
    ex = Executor(store, optimize=True)
    prepared = ex._prepared(plan())
    assert prepared.op == "fused_difference"
    assert len(prepared.params["pre_stages"]) == 1
    assert len(prepared.params["right_pre_stages"]) == 1
    ex.evaluate(plan())
    assert ex.stats.barrier_fused_groups == 1
    assert ex.stats.producer_stage_ops == 2


def test_fused_consumer_chain_filters_keep_mask_before_gather(monkeypatch):
    data, domains = _chain_case(4)
    f = Frame.from_pydict(data, domains=domains)
    plan = lambda: alg.Projection(
        alg.Selection(alg.DropDuplicates(alg.Source("src"), None),
                      alg.col("k") > alg.lit(0)), ("k", "x"))
    pdf = _to_pandas(data).drop_duplicates()
    keep = [v is not None and v > 0 for v in pdf["k"]]
    expected = _pd_lists(pdf[np.asarray(keep, dtype=bool)][["k", "x"]])
    _sweep(plan, {"src": f}, expected, "consumer-dedup", monkeypatch)
    # THE consumer-fusion win: strictly fewer rows materialized than unfused
    store = {"src": PartitionedFrame.from_frame(f, row_parts=4)}
    exf = Executor(store, optimize=True)
    exu = Executor(store, optimize=False)
    prepared = exf._prepared(plan())
    assert prepared.op == "fused_drop_duplicates"
    assert len(prepared.params["post_stages"]) == 2
    exf.evaluate(plan())
    exu.evaluate(plan())
    assert 0 < exf.stats.gather_rows < exu.stats.gather_rows
    assert exf.stats.consumer_stage_ops == 2


def test_no_to_frame_on_dedup_inputs(monkeypatch):
    """The acceptance criterion itself: the block-parallel paths never
    concatenate their inputs."""
    data, domains = _chain_case(6)
    f = Frame.from_pydict(data, domains=domains)
    store = {"l": PartitionedFrame.from_frame(f, row_parts=4),
             "r": PartitionedFrame.from_frame(f, row_parts=3)}
    calls = []
    orig = PartitionedFrame.to_frame

    def spy(self):
        calls.append(1)
        return orig(self)

    monkeypatch.setattr(PartitionedFrame, "to_frame", spy)
    Executor(store).evaluate(alg.DropDuplicates(alg.Source("l"), None))
    Executor(store).evaluate(alg.Difference(alg.Source("l"), alg.Source("r")))
    assert not calls


def test_dedup_api_level(eager_session):
    """Fluent-API round trip (session history + MQO path included)."""
    from repro.core.api import from_pydict
    df = from_pydict({"k": [1, 2, 1, 2, 3], "x": [0.5, 1.5, 0.5, 1.5, 2.5]})
    assert df.drop_duplicates().collect().col("k").to_pylist() == [1, 2, 3]
    other = from_pydict({"k": [2], "x": [1.5]})
    assert df.difference(other).collect().col("k").to_pylist() == [1, 1, 3]
    assert df.drop_duplicates(subset=["x"]).collect().col("k").to_pylist() == [1, 2, 3]
