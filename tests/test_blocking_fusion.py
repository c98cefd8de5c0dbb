"""Barrier fusion: row-local chains fused THROUGH blocking operators.

Invariants:
  * fused and unfused plans are **result-equivalent** (values, labels, null
    masks) for producer-into-GROUPBY, consumer-after-SORT/JOIN, and
    WINDOW-carry chains, over multi-block grids;
  * consumer fusion gathers strictly fewer payload rows than the unfused
    path on selective chains (``ExecStats.gather_rows``);
  * WINDOW carry composition at partition seams survives pre/post stage
    fusion (block boundaries are invisible in the result);
  * null masks propagate through fused selections exactly as per-node;
  * MQO: a sub-plan recorded in the session statement history splits the
    fused group so the materialization cache still serves the shared prefix;
  * counter invariant: ``fused_stage_ops`` == pipeline stage ops
    + ``producer_stage_ops`` + ``consumer_stage_ops`` (one source of truth);
  * jit-traced whole-chain map runs are adopted only when bit-identical to
    the eager path; host-numpy udf chains fall back and stay correct.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import algebra as alg
from repro.core import physical, rewrite
from repro.core.dtypes import Domain
from repro.core.executor import Executor
from repro.core.frame import Column, Frame
from repro.core.partition import PartitionedFrame
from repro.core.session import EvalMode, Session


def _mk_frame(n=211, with_nulls=True, seed=11):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 6, n).astype(object)
    v = rng.integers(-50, 50, n).astype(object)
    x = rng.standard_normal(n).astype(np.float32).astype(object)
    s = np.asarray([("a", "b", "c")[i % 3] for i in range(n)], dtype=object)
    if with_nulls:
        for arr, step in ((k, 17), (v, 13), (x, 7)):
            arr[::step] = None
    return Frame.from_pydict({
        "k": k.tolist(), "v": v.tolist(), "x": x.tolist(), "s": s.tolist(),
    }, row_labels=[f"r{i}" for i in range(n)])


def _scale_udf(name="x", a=2.0, b=1.0):
    def fn(cols, frame):
        out = dict(cols)
        c = cols[name]
        out[name] = Column(c.data * a + b, Domain.FLOAT, c.mask, None)
        return out
    return alg.Udf(name=f"scale_{name}_{a}_{b}", fn=fn,
                   deps=frozenset([name]), elementwise=True)


def _both(plan, store):
    fused_ex = Executor(store, optimize=True)
    plain_ex = Executor(store, optimize=False)
    a = fused_ex.evaluate(plan).to_frame()
    b = plain_ex.evaluate(plan).to_frame()
    return a, b, fused_ex, plain_ex


def _assert_frames_equal(a: Frame, b: Frame):
    assert a.col_labels.to_list() == b.col_labels.to_list()
    assert a.row_labels.to_list() == b.row_labels.to_list()
    ad, bd = a.to_pydict(), b.to_pydict()
    for name in ad:
        av, bv = ad[name], bd[name]
        assert [x is None for x in av] == [x is None for x in bv], name
        fa = np.asarray([0 if x is None else x for x in av])
        fb = np.asarray([0 if x is None else x for x in bv])
        np.testing.assert_array_equal(fa, fb, err_msg=str(name))


# -----------------------------------------------------------------------------
# producer fusion into GROUPBY
# -----------------------------------------------------------------------------
@pytest.mark.parametrize("row_parts", [1, 4, 7])
def test_producer_into_groupby_dense_int_key(row_parts):
    f = _mk_frame()
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=row_parts)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.GroupBy(
        alg.Selection(alg.Map(src, _scale_udf()), alg.col("v") > alg.lit(0)),
        ("k",),
        [("x", "sum", "xs"), ("x", "mean", "xm"), ("v", "min", "vmin"),
         ("v", "max", "vmax"), ("v", "count", "vc"), ("x", "std", "xstd")])
    a, b, fx, _ = _both(plan, store)
    assert fx.stats.barrier_fused_groups == 1
    assert fx.stats.producer_stage_ops == 2
    assert fx._prepared(plan).op == "fused_groupby"
    _assert_frames_equal(a, b)


def test_producer_into_groupby_under_pallas_kernels(use_pallas_kernels):
    # the combined partial program (kernels.ops.segment_reduce_multi) must
    # also lower through the Pallas kernels (interpret mode on CPU): the
    # dispatch mode is part of its jit cache key
    f = _mk_frame(120)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.GroupBy(alg.Selection(src, alg.col("v") > alg.lit(0)),
                       ("k",), [("x", "sum", "xs"), ("v", "max", "vx")])
    a, b, fx, _ = _both(plan, store)
    assert fx._prepared(plan).op == "fused_groupby"
    _assert_frames_equal(a, b)


def test_producer_into_groupby_string_key_general_path():
    # a coded (string) key cannot take the fused single-INT path: the
    # groupby's own key step (dense codes from the dictionary) must still
    # run over the staged (fused) blocks
    f = _mk_frame()
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=5)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.GroupBy(
        alg.Selection(alg.Map(src, _scale_udf()), alg.col("v") > alg.lit(-10)),
        ("s",), [("x", "sum", "xs"), ("v", "mean", "vm")])
    a, b, fx, _ = _both(plan, store)
    assert fx.stats.barrier_fused_groups == 1
    _assert_frames_equal(a, b)


def test_producer_into_groupby_null_keys_dropped():
    # rows whose key is null must vanish from the aggregate either way
    f = _mk_frame(with_nulls=True)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.GroupBy(alg.Selection(src, alg.col("v") != alg.lit(3)),
                       ("k",), [("v", "sum", "vs")])
    a, b, fx, _ = _both(plan, store)
    assert fx._prepared(plan).op == "fused_groupby"   # lone op absorbed too
    _assert_frames_equal(a, b)


def test_producer_into_groupby_empty_selection():
    f = _mk_frame(64, with_nulls=False)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.GroupBy(alg.Selection(src, alg.col("v") > alg.lit(10 ** 6)),
                       ("k",), [("v", "sum", "vs")])
    a, b, _, _ = _both(plan, store)
    assert a.nrows == b.nrows == 0


# -----------------------------------------------------------------------------
# consumer fusion after SORT / JOIN
# -----------------------------------------------------------------------------
@pytest.mark.parametrize("row_parts", [1, 4])
def test_consumer_after_sort_filters_index_before_gather(row_parts):
    f = _mk_frame()
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=row_parts)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.Projection(
        alg.Selection(alg.Sort(src, ("v",)), alg.col("v") > alg.lit(5)),
        ("k", "v"))
    a, b, fx, px = _both(plan, store)
    assert fx._prepared(plan).op == "fused_sort"
    # THE consumer-fusion win: strictly fewer payload rows gathered
    assert 0 < fx.stats.gather_rows < px.stats.gather_rows
    assert px.stats.gather_rows == f.nrows
    _assert_frames_equal(a, b)


def test_consumer_after_sort_with_trailing_map():
    f = _mk_frame()
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.Map(
        alg.Selection(alg.Sort(src, ("v",), ascending=False),
                      alg.col("x").notna()),
        _scale_udf())
    a, b, fx, _ = _both(plan, store)
    assert fx._prepared(plan).op == "fused_sort"
    _assert_frames_equal(a, b)


@pytest.mark.parametrize("how", ["inner", "left", "outer"])
def test_consumer_after_join_filters_match_index(how):
    f = _mk_frame(97)
    g = Frame.from_pydict({"k": [0, 1, 2, 3, 9],
                           "w": [10.0, None, 30.0, 40.0, 50.0]})
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3),
             "f1": PartitionedFrame.from_frame(g)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    src2 = alg.Source("f1", nrows=g.nrows, ncols=g.ncols)
    plan = alg.Selection(alg.Join(src, src2, on=("k",), how=how),
                         alg.col("w") > alg.lit(15.0))
    a, b, fx, px = _both(plan, store)
    assert fx._prepared(plan).op == "fused_join"
    assert fx.stats.gather_rows < px.stats.gather_rows
    _assert_frames_equal(a, b)


def test_consumer_after_join_projection_prunes_gather():
    f = _mk_frame(80, with_nulls=False)
    g = Frame.from_pydict({"k": [0, 1, 2], "w": [1.0, 2.0, 3.0]})
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=2),
             "f1": PartitionedFrame.from_frame(g)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    src2 = alg.Source("f1", nrows=g.nrows, ncols=g.ncols)
    plan = alg.Projection(
        alg.Selection(alg.Join(src, src2, on=("k",), how="inner"),
                      alg.col("v") > alg.lit(0)),
        ("k", "w"))
    a, b, fx, _ = _both(plan, store)
    assert fx._prepared(plan).op == "fused_join"
    _assert_frames_equal(a, b)


# -----------------------------------------------------------------------------
# WINDOW stage fusion with carry composition at seams
# -----------------------------------------------------------------------------
@pytest.mark.parametrize("func", ["cumsum", "cummax", "cummin", "cumprod"])
def test_window_scan_chain_seams(func):
    f = _mk_frame(150)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=6)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.Map(
        alg.Window(alg.Selection(src, alg.col("v") % alg.lit(3) != alg.lit(0)),
                   func, ("x",)),
        _scale_udf())
    a, b, fx, _ = _both(plan, store)
    prep = fx._prepared(plan)
    assert prep.op == "fused_window"
    assert [s.op for s in prep.pre_stages] == ["selection"]
    assert [s.op for s in prep.post_stages] == ["map"]
    _assert_frames_equal(a, b)


def test_window_seam_exactness_single_vs_many_blocks():
    # block boundaries must be invisible: the fused multi-block result equals
    # the single-block result row for row
    f = _mk_frame(120, with_nulls=False)
    src_cols = f.nrows, f.ncols
    plan_of = lambda src: alg.Map(
        alg.Window(alg.Selection(src, alg.col("v") > alg.lit(-100)),
                   "cumsum", ("x",)), _scale_udf())
    multi = {"f0": PartitionedFrame.from_frame(f, row_parts=8)}
    single = {"f0": PartitionedFrame.from_frame(f, row_parts=1)}
    src = alg.Source("f0", nrows=src_cols[0], ncols=src_cols[1])
    a = Executor(multi, optimize=True).evaluate(plan_of(src)).to_frame()
    b = Executor(single, optimize=True).evaluate(plan_of(src)).to_frame()
    ad = np.asarray(a.to_pydict()["x"], dtype=np.float32)
    bd = np.asarray(b.to_pydict()["x"], dtype=np.float32)
    np.testing.assert_allclose(ad, bd, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("func,size", [("diff", None), ("shift", None),
                                       ("rolling_sum", 8)])
def test_window_halo_and_rolling_chains(func, size):
    f = _mk_frame(100)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=4)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.Map(
        alg.Window(alg.Selection(src, alg.col("v").notna()), func, ("x",),
                   size=size, periods=2),
        _scale_udf())
    a, b, fx, _ = _both(plan, store)
    assert fx._prepared(plan).op == "fused_window"
    _assert_frames_equal(a, b)


def test_fused_window_stays_prefix_safe():
    # barrier-fusing a forward window must not disable §6.1.2 prefix
    # evaluation: head(k) on the fused plan still touches only a prefix
    f = _mk_frame(300, with_nulls=False)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=6)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    plan = alg.Map(alg.Window(alg.Selection(src, alg.col("v") > alg.lit(-200)),
                              "cumsum", ("x",)), _scale_udf())
    ex = Executor(store, optimize=True)
    assert ex._prepared(plan).op == "fused_window"
    got = ex.evaluate_prefix(plan, 4).to_frame().head(4).to_pydict()
    assert ex.stats.prefix_evals == 1, "fused window fell back to full eval"
    want = Executor(store, optimize=False).evaluate(plan).to_frame().head(4).to_pydict()
    np.testing.assert_allclose(np.asarray(got["x"], dtype=np.float32),
                               np.asarray(want["x"], dtype=np.float32), rtol=1e-5)


# -----------------------------------------------------------------------------
# MQO-aware fusion boundaries (session statement history)
# -----------------------------------------------------------------------------
def test_history_splits_fused_group_and_reuses_cache():
    f = _mk_frame(128, with_nulls=False)
    sess = Session(mode=EvalMode.LAZY)
    src = sess.register_frame(PartitionedFrame.from_frame(f, row_parts=3))

    shared = alg.Selection(alg.Map(src, _scale_udf()), alg.col("v") > alg.lit(0))
    sess.statement(shared)
    r_shared = sess.collect(shared)

    plan = alg.GroupBy(shared, ("k",), [("x", "sum", "xs")])
    prep = sess.executor._prepared(plan)
    # the shared prefix is NOT absorbed into the groupby: split at history
    assert prep.op == "groupby"
    assert prep.children[0].op == "fused_pipeline"
    hits = sess.executor.stats.cache_hits
    out = sess.collect(plan)
    assert sess.executor.stats.cache_hits > hits   # prefix served from cache

    # a fresh session with no history fuses straight through
    sess2 = Session(mode=EvalMode.LAZY)
    src2 = sess2.register_frame(PartitionedFrame.from_frame(f, row_parts=3))
    shared2 = alg.Selection(alg.Map(src2, _scale_udf()), alg.col("v") > alg.lit(0))
    plan2 = alg.GroupBy(shared2, ("k",), [("x", "sum", "xs")])
    assert sess2.executor._prepared(plan2).op == "fused_groupby"
    # and both strategies agree on the result
    out2 = sess2.collect(plan2)
    _assert_frames_equal(out, out2)
    sess.close()
    sess2.close()


def test_resubmitting_same_statement_reproduces_fused_key():
    # a statement must never act as a fusion barrier against itself: the
    # second submission re-fuses to the identical plan and hits the cache
    f = _mk_frame(90, with_nulls=False)
    sess = Session(mode=EvalMode.EAGER)
    src = sess.register_frame(PartitionedFrame.from_frame(f, row_parts=2))
    plan = alg.GroupBy(alg.Selection(src, alg.col("v") > alg.lit(0)),
                       ("k",), [("v", "sum", "vs")])
    sess.statement(plan)
    evaluated = sess.executor.stats.evaluated_nodes
    sess.statement(plan)
    assert sess.executor.stats.evaluated_nodes == evaluated  # pure cache hit
    sess.close()


# -----------------------------------------------------------------------------
# counters: one source of truth
# -----------------------------------------------------------------------------
def test_counter_invariant_across_mixed_plan():
    f = _mk_frame(96, with_nulls=False)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=2)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    g = alg.GroupBy(alg.Selection(alg.Map(src, _scale_udf()),
                                  alg.col("v") > alg.lit(0)),
                    ("k",), [("x", "sum", "xs")])
    plan = alg.Rename(alg.Selection(g, alg.col("xs") > alg.lit(0.0)),
                      {"xs": "total"})
    out, fs = rewrite.fuse_pipelines(plan)
    pipeline_ops = sum(len(n.params["stages"]) for n in out.walk()
                      if n.op == "fused_pipeline")
    assert fs.fused_ops == pipeline_ops + fs.producer_ops + fs.consumer_ops
    assert fs.barrier_groups == 1 and fs.producer_ops == 2
    assert fs.groups == 1   # the consumer chain above the groupby

    ex = Executor(store, optimize=True)
    ex.evaluate(plan)
    assert ex.stats.fused_stage_ops == (
        pipeline_ops + ex.stats.producer_stage_ops + ex.stats.consumer_stage_ops)


def test_shared_blocking_node_not_absorbed():
    # two consumers of one SORT: absorbing it into either chain would
    # re-execute the sort per branch
    f = _mk_frame(60, with_nulls=False)
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=2)}
    src = alg.Source("f0", nrows=f.nrows, ncols=f.ncols)
    srt = alg.Sort(src, ("v",))
    b1 = alg.Projection(alg.Selection(srt, alg.col("v") > alg.lit(0)), ("v",))
    b2 = alg.Projection(alg.Selection(srt, alg.col("v") < alg.lit(0)), ("v",))
    plan = alg.Union(b1, b2)
    out, fs = rewrite.fuse_pipelines(plan)
    assert fs.barrier_groups == 0
    assert sum(1 for n in out.walk() if n.op == "sort") == 1
    a, b, _, _ = _both(plan, store)
    _assert_frames_equal(a, b)


# -----------------------------------------------------------------------------
# jit-traced whole-chain map runs
# -----------------------------------------------------------------------------
def test_map_run_jit_adopted_and_bit_identical(monkeypatch):
    monkeypatch.setenv("REPRO_JIT_UDFS", "1")   # CPU defaults to eager
    physical._MAP_JIT.clear()
    f = Frame.from_pydict({"a": [1.5, 2.5, 3.5, 4.5, 5.5, 6.5],
                           "b": [1, 2, 3, 4, 5, 6]})
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=3)}
    src = alg.Source("f0", nrows=6, ncols=2)
    u1 = alg.Udf(name="jit1", elementwise=True, fn=lambda c, fr: {
        "a": Column(c["a"].data + 1.0, Domain.FLOAT), "b": c["b"]})
    u2 = alg.Udf(name="jit2", elementwise=True, fn=lambda c, fr: {
        "a": Column(c["a"].data * 3.0, Domain.FLOAT), "b": c["b"]})
    plan = alg.Map(alg.Selection(alg.Map(src, u1), alg.col("a") > alg.lit(2.0)), u2)
    a, b, _, _ = _both(plan, store)
    _assert_frames_equal(a, b)
    assert any(v is not None for v in physical._MAP_JIT.values()), \
        "no map chain adopted a compiled program"


def test_map_run_host_numpy_falls_back(monkeypatch):
    monkeypatch.setenv("REPRO_JIT_UDFS", "1")
    physical._MAP_JIT.clear()
    f = Frame.from_pydict({"a": [1.5, 2.5, 3.5, 4.5]})
    store = {"f0": PartitionedFrame.from_frame(f, row_parts=2)}
    src = alg.Source("f0", nrows=4, ncols=1)
    # np.asarray on a tracer raises → per-chain fallback to eager dispatch
    uh = alg.Udf(name="hostnp", elementwise=True, fn=lambda c, fr: {
        "a": Column(jnp.asarray(np.asarray(c["a"].data) ** 2), Domain.FLOAT)})
    plan = alg.Selection(alg.Map(src, uh), alg.col("a") > alg.lit(3.0))
    a, b, _, _ = _both(plan, store)
    _assert_frames_equal(a, b)
    keys = [k for k in physical._MAP_JIT
            if any(u[1] == "hostnp" for u in k[0])]
    assert keys and all(physical._MAP_JIT[k] is None for k in keys), \
        "host-numpy chain should be marked eager-only"
