"""Statement tracing & metrics layer (observability PR).

The tracer must be invisible when off (the autouse conftest guard watches
``trace.recorded_total()`` in every OTHER test of the suite) and exact when
on: span parenting survives pool-thread hops, retries/cancellation/shutdown
never leak open spans, the Chrome export validates against the trace-event
schema, and per-statement counter deltas attached to spans sum exactly to
the global ``ExecStats`` movement — even under a seeded chaos plan with a
4x-over-budget spill pipeline.
"""
import dataclasses
import json
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import compile_cache
from repro.core import EvalMode, Session
from repro.core import algebra as alg
from repro.core import api, faults, schedule, trace
from repro.core.algebra import GroupBy, Map, Selection, Udf, col, lit
from repro.core.dtypes import Domain
from repro.core.executor import ExecStats, StatsTee
from repro.core.faults import StatementCancelled
from repro.core.frame import Column, Frame
from repro.core.labels import RangeLabels, labels_from_values
from repro.core.service import QueryService
from repro.core.transfer import note_h2d, to_host
from repro.kernels import ops as kops

pytestmark = pytest.mark.trace

_DELTA_KEYS = ("spills", "faults", "spilled_bytes", "checksum_failures",
               "recomputed_blocks", "budget_overruns", "faults_injected",
               "d2h_bytes", "d2h_copies", "h2d_bytes", "compiles",
               "compile_ns")


@pytest.fixture(autouse=True)
def clean_trace(monkeypatch):
    """Isolate the process tracer state around every test here."""
    for knob in ("REPRO_TRACE", "REPRO_TRACE_RING", "REPRO_FAULT_PLAN",
                 "REPRO_FAULT_SEED", "REPRO_MEM_BUDGET"):
        monkeypatch.delenv(knob, raising=False)
    trace.reset()
    faults.reset()
    yield monkeypatch
    trace.reset()
    faults.reset()
    schedule.reset_pool()


def _frame(n=120, seed=0):
    rng = np.random.default_rng(seed)
    return Frame(
        [Column(np.asarray(rng.integers(0, 8, n, dtype=np.int32)), Domain.INT),
         Column(np.asarray((rng.integers(0, 12, n) * np.float32(0.25))
                           .astype(np.float32)), Domain.FLOAT)],
        RangeLabels(n), labels_from_values(["k", "x"]))


def _plan(src, name="trace_scale"):
    def fn(cols, frame):
        out = dict(cols)
        c = cols["x"]
        out["x"] = Column(c.data * 2.0 + 1.0, Domain.FLOAT, c.mask, None)
        return out

    udf = Udf(name=name, fn=fn, deps=frozenset(["x"]), elementwise=True)
    return GroupBy(Selection(Map(src, udf), col("k") < lit(6)),
                   ("k",), [("x", "sum", "xs"), ("x", "count", "n")])


def _slow_plan(src, delay_s, started=None, release=None, name="trace_slow"):
    def fn(cols, frame):
        if started is not None:
            started.set()
        if release is not None:
            release.wait(10.0)
        time.sleep(delay_s)
        return dict(cols)

    return Map(src, Udf(name=name, fn=fn, deps=frozenset(["x"]),
                        elementwise=True))


def _drain_open(tr, timeout=10.0):
    """Unwinding worker threads close their spans asynchronously."""
    deadline = time.monotonic() + timeout
    while tr.open_spans() and time.monotonic() < deadline:
        time.sleep(0.01)
    return tr.open_spans()


# =============================================================================
# disabled path: a true no-op
# =============================================================================
def test_disabled_records_nothing():
    before = trace.recorded_total()
    assert trace.current() is None
    s = Session(mode=EvalMode.LAZY)
    try:
        src = s.register_frame(_frame(200, seed=1), row_parts=4)
        assert s.collect(_plan(src)).nrows > 0
        assert s.tracer is None
        assert s.explain_stats()["traced"] is False
    finally:
        s.close()
    assert trace.recorded_total() == before


def test_session_trace_false_forces_off(clean_trace):
    clean_trace.setenv("REPRO_TRACE", "1")
    trace.reset()
    assert isinstance(trace.current(), trace.Tracer)   # process tracer on
    s = Session(mode=EvalMode.LAZY, trace=False)
    try:
        assert s.tracer is None                        # session forced off
    finally:
        s.close()


# =============================================================================
# span parenting: plan → dispatch → pool-thread chunks
# =============================================================================
def test_span_parenting_across_pool_threads(clean_trace):
    clean_trace.setenv("REPRO_POOL_WORKERS", "2")
    schedule.reset_pool()
    tr = trace.Tracer(session_id="t")
    trace.configure(tr)
    try:
        with schedule.node_scope("parenting"):
            out = schedule.dispatch_blocks(lambda x: x * 2, list(range(16)))
        assert out == [i * 2 for i in range(16)]
    finally:
        trace.reset()
    spans = tr.snapshot()
    disp = [s for s in spans if s.cat == "dispatch"]
    chunks = [s for s in spans if s.cat == "task"]
    assert len(disp) == 1 and chunks
    assert disp[0].args["blocks"] == 16
    assert disp[0].args["chunks"] == len(chunks)
    for c in chunks:
        assert c.parent == disp[0].id            # carried via propagate()
        assert c.stmt == disp[0].stmt
    assert {c.tid for c in chunks} != {disp[0].tid}   # crossed threads
    assert sum(c.args["blocks"] for c in chunks) == 16
    assert tr.open_spans() == 0


def test_failed_chunk_split_retry_records_backoff_spans(clean_trace):
    clean_trace.setenv("REPRO_POOL_WORKERS", "2")
    clean_trace.setenv("REPRO_RETRY_BACKOFF_MS", "1")
    schedule.reset_pool()
    ref = schedule.dispatch_blocks(lambda x: x * 2, list(range(16)))
    clean_trace.setenv("REPRO_FAULT_PLAN", "worker:0.5")
    clean_trace.setenv("REPRO_FAULT_SEED", "3")
    tr = trace.Tracer(session_id="t")
    trace.configure(tr)
    st = ExecStats()
    try:
        got = schedule.dispatch_blocks(lambda x: x * 2, list(range(16)),
                                       stats=st)
    finally:
        trace.reset()
    assert got == ref                            # chaos recovered, identical
    assert st.retries > 0
    retries = [s for s in tr.snapshot() if s.cat == "retry"]
    assert len(retries) == st.retries            # one backoff span per retry
    stmts = {s.stmt for s in tr.snapshot()}
    assert len(stmts) == 1                       # all under one statement
    for r in retries:
        assert r.args["attempt"] >= 1 and "block" in r.args
    assert tr.open_spans() == 0


# =============================================================================
# cancellation / shutdown: spans never leak open
# =============================================================================
def test_cancellation_closes_open_spans():
    s = Session(mode=EvalMode.LAZY, trace=True)
    tr = s.tracer
    try:
        started = threading.Event()
        src = s.register_frame(_frame(64, seed=4), row_parts=8)
        h = s.submit(_slow_plan(src, 0.15, started=started,
                                name="trace_cancel"))
        assert started.wait(5.0)
        h.cancel()
        with pytest.raises(StatementCancelled):
            h.result(timeout=10.0)
        assert _drain_open(tr) == 0
        errs = [sp for sp in tr.snapshot()
                if sp.args and "error" in sp.args]
        assert any("Cancel" in sp.args["error"] for sp in errs)
    finally:
        s.close()


def test_executor_shutdown_mid_statement_closes_spans():
    s = Session(mode=EvalMode.LAZY, trace=True)
    tr = s.tracer
    started, release = threading.Event(), threading.Event()
    src = s.register_frame(_frame(48, seed=6), row_parts=4)
    s.submit(_slow_plan(src, 0.0, started=started, release=release,
                        name="trace_close"))
    assert started.wait(5.0)
    try:
        s.close()                                # shutdown under the statement
    finally:
        release.set()
    assert _drain_open(tr) == 0                  # every span closed on unwind


# =============================================================================
# profile / explain surfaces
# =============================================================================
def test_statement_profile_and_explain_stats():
    s = Session(mode=EvalMode.LAZY, trace=True)
    try:
        src = s.register_frame(_frame(300, seed=7), row_parts=4)
        h = s.submit(_plan(src, name="trace_prof"))
        h.result(timeout=30.0)
        prof = h.profile()
        assert prof is not None and prof["stmt"] == h.stmt_id
        assert prof["wall_ns"] > 0 and prof["spans"] > 0
        assert prof["nodes"]                     # per-node attribution
        assert prof["dispatch"]["dispatched_blocks"] > 0
        ex = s.explain_stats(h.stmt_id)
        assert ex["traced"] is True
        assert ex["profile"]["stmt"] == h.stmt_id
        assert ex["stats"]["metrics"]["evaluated_nodes"] > 0
        assert ex["stats"]["metrics"]["node_wall_ns"] > 0
        # timing counters move even with tracing off (always-on ExecStats)
        assert s.stats.plan_prep_ns >= 0
    finally:
        s.close()


@pytest.mark.spill
def test_counter_deltas_sum_exactly_under_chaos(tmp_path):
    import repro.core.api as api
    n = 50_000
    data = {"a": np.arange(n, dtype=np.float64),
            "b": (np.arange(n) % 97).astype(np.float64)}
    s = Session(mode=EvalMode.LAZY, trace=True, mem_budget_bytes=n * 8 // 2,
                spill_dir=str(tmp_path),
                fault_plan="worker:0.2,corrupt:0.5,enospc:0.5", fault_seed=7)
    try:
        df = api.from_pydict(data, session=s)
        q = df[df["a"] > 1000.0].groupby("b").agg({"a": ["sum", "mean"]})
        st0 = dataclasses.replace(s.stats)
        q.collect()
        st1, tr = s.stats, s.tracer
        assert tr.open_spans() == 0
        totals = tr.counter_totals(tr.last_stmt)
        for k in _DELTA_KEYS:
            assert totals.get(k, 0) == getattr(st1, k) - getattr(st0, k), k
        # the transfer counters moved, so the sums above are not 0 == 0
        assert st1.d2h_copies > st0.d2h_copies
        assert st1.h2d_bytes > st0.h2d_bytes
    finally:
        s.close()
    leftovers = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert leftovers == []                       # zero leaked spill files


# =============================================================================
# Chrome trace export
# =============================================================================
def test_chrome_export_validates_and_names_threads(clean_trace, tmp_path):
    clean_trace.setenv("REPRO_POOL_WORKERS", "2")
    schedule.reset_pool()
    s = Session(mode=EvalMode.LAZY, trace=True)
    try:
        src = s.register_frame(_frame(300, seed=8), row_parts=4)
        assert s.collect(_plan(src, name="trace_export")).nrows > 0
        path = s.trace_json(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
    finally:
        s.close()
    n = trace.validate_chrome_trace(doc)
    assert n == len(doc["traceEvents"]) and n > 0
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert "X" in phases and "M" in phases       # spans + thread names
    names = [e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"]
    assert names and all(isinstance(x, str) and x for x in names)
    durs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(isinstance(e["ts"], (int, float)) and e["dur"] >= 0
               for e in durs)


def test_chrome_validation_rejects_malformed():
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"no": "events"})
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x"}]})   # missing ts/dur
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(
            {"traceEvents": [{"ph": "?", "name": "x", "pid": 1, "tid": 1,
                              "ts": 0}]})                  # unknown phase


def test_ring_buffer_bounds_retention():
    tr = trace.Tracer(ring=4, session_id="ring")
    before = trace.recorded_total()
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.snapshot()) == 4               # bounded retention
    assert trace.recorded_total() == before + 10  # but every record counted
    assert tr.open_spans() == 0


# =============================================================================
# metrics registry (shared shape: core ExecStats + serve engine)
# =============================================================================
def test_metrics_registry_shape_and_serve_unification():
    m = trace.Metrics("m", steps=0)
    m.inc("steps")
    m["tokens_out"] = 3
    m.gauge("depth", 7)
    assert m["steps"] == 1 and m["missing"] == 0
    assert dict(m) == {"steps": 1, "tokens_out": 3, "depth": 7}
    exp = m.export()
    assert exp["name"] == "m" and exp["metrics"]["tokens_out"] == 3

    st = ExecStats()
    st.evaluated_nodes = 5
    proj = trace.stats_metrics(st)
    assert set(proj.export()) == set(exp)        # ONE export shape
    assert proj["evaluated_nodes"] == 5

    from repro.serve import engine as serve_engine
    assert serve_engine.Metrics is trace.Metrics  # serve tier unified


# =============================================================================
# service: admission phases + per-tenant attribution
# =============================================================================
def test_service_tenant_report_and_admission_phases():
    with QueryService(background_workers=2) as svc:
        busy = svc.session(mode=EvalMode.LAZY)
        idle = svc.session(mode=EvalMode.LAZY)
        src = busy.register_frame(_frame(400, seed=9), row_parts=4)
        busy.submit(_plan(src, name="trace_tenant")).result(timeout=30.0)
        rows = svc.tenant_report()
        assert len(rows) == 2
        by_sid = {r["session"]: r for r in rows}
        bid = busy.config.session_id
        assert by_sid[bid]["evaluated_nodes"] > 0
        assert by_sid[bid]["node_wall_ns"] > 0
        assert by_sid[bid]["slot_hold_ns"] > 0   # admission slot was held
        assert by_sid[bid]["queue_wait_ns"] >= 0
        assert by_sid[idle.config.session_id]["evaluated_nodes"] == 0
        assert rows[0]["session"] == bid         # pool-pressure sort
        # the per-tenant gauges sum to the service-global timing counters
        assert sum(r["slot_hold_ns"] for r in rows) == svc.stats.slot_hold_ns
        assert sum(r["node_wall_ns"] for r in rows) == svc.stats.node_wall_ns


def test_service_traced_statement_records_admission_spans():
    with QueryService(background_workers=2) as svc:
        tr = trace.Tracer(session_id="tenant")
        s = svc.session(mode=EvalMode.LAZY, trace=tr)
        src = s.register_frame(_frame(200, seed=11), row_parts=4)
        h = s.submit(_plan(src, name="trace_admit"))
        h.result(timeout=30.0)
        assert _drain_open(tr) == 0
        names = {sp.name for sp in tr.snapshot()}
        assert "queue_wait" in names and "slot_hold" in names
        prof = tr.profile(h.stmt_id)
        assert prof["service"]["slot_hold_ns"] > 0


# =============================================================================
# phase spans inside the operators; transfer and compile counters
# =============================================================================
def _q1_like(s, keys=("flag", "status"), n=3000, seed=12):
    """A Q1-shaped statement: filter, two computed columns, a two-key
    groupby — over category keys (dense codes) by default."""
    rng = np.random.default_rng(seed)
    df = api.from_pydict({
        "flag": rng.choice(["A", "N", "R"], n).tolist(),
        "status": rng.choice(["F", "O"], n).tolist(),
        "price": (rng.random(n) * 100 + 0.5).tolist(),
        "disc": (rng.integers(0, 11, n) / 100 + 0.001).tolist(),
        "tax": (rng.integers(0, 9, n) / 100 + 0.001).tolist(),
        "ship": rng.integers(0, 200, n).tolist()}, session=s)
    f = df[df["ship"] <= 150]
    f["net"] = f["price"] * (f["disc"] * -1.0 + 1.0)
    return f.groupby(list(keys)).agg(
        {"price": ["sum", "mean"], "net": ["sum"]})


def test_phase_is_shared_null_context_when_off():
    assert trace.current() is None
    assert trace.phase("groupby:keys") is trace.phase("stage:map")


# two category keys take dense codes (per-block code spans on pool threads,
# no sort); two FLOAT keys take the general factorization, whose per-column
# uniques run as pool tasks under the caller's key step
@pytest.mark.parametrize("keys", [("flag", "status"), ("disc", "tax")],
                         ids=["dense_codes", "factorized"])
def test_fused_groupby_records_phase_spans_under_its_node(keys):
    s = Session(mode=EvalMode.LAZY, trace=True, default_row_parts=3)
    try:
        _q1_like(s, keys).collect()
        tr = s.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
    finally:
        s.close()
    by_id = {sp.id: sp for sp in spans}
    node = [sp for sp in spans if sp.name == "eval:fused_groupby"]
    assert len(node) == 1
    steps = {}
    for sp in spans:               # the steps on the caller thread
        if sp.cat == "phase" and sp.parent == node[0].id:
            steps.setdefault(sp.name, []).append(sp)
    for name in ("groupby:resolve", "groupby:keys", "groupby:combine",
                 "groupby:finalize"):
        assert len(steps.get(name, ())) == 1, name
    uniq = [sp for sp in spans if sp.name == "keys:unique"]
    block_keys = [sp for sp in spans if sp.name == "groupby:keys"
                  and sp.parent != node[0].id]
    if keys == ("flag", "status"):
        assert uniq == []                                # no sort at all
        # each block's codes are computed in its partial task, in a chunk
        assert block_keys
        for sp in block_keys:
            chunk = by_id[sp.parent]
            assert chunk.name == "chunk:fused_groupby"
            assert by_id[chunk.parent].parent == node[0].id
    else:
        assert block_keys == []
        # the per-column key uniques run on pool threads, under groupby:keys
        assert len(uniq) == 2                            # one per key column
        for sp in uniq:
            chunk = by_id[sp.parent]
            assert by_id[by_id[chunk.parent].parent] is steps["groupby:keys"][0]
    stages = [sp for sp in spans if sp.name in ("stage:select", "stage:map")]
    masked = [sp for sp in spans if sp.name == "groupby:stages"]
    if keys == ("flag", "status"):
        # dense keys of source columns: the selection is a device code mask,
        # one groupby:stages per block partial task, and no staged sweep
        assert stages == []
        assert len(masked) == 3                          # one per block
        stages = masked
    else:
        assert masked == []
        assert {sp.name for sp in stages} == {"stage:select", "stage:map"}
    for sp in stages:                                    # inside pool chunks
        assert by_id[sp.parent].name == "chunk:fused_groupby"
        assert by_id[by_id[sp.parent].parent].parent == node[0].id
    for sp in spans:     # one family per step in the idle-gap breakdown
        assert not sp.name.partition(":")[2][:1].isdigit(), sp.name


def test_host_take_counts_a_device_block_once():
    col = Column(jnp.arange(1000, dtype=jnp.int32), Domain.INT)
    st = ExecStats()
    with schedule.stats_scope(st):
        col.take(np.arange(10))
        assert (st.d2h_bytes, st.d2h_copies) == (4000, 1)
        col.take(np.arange(5, 50))           # the host copy is cached
        col.filter(np.ones(1000, dtype=bool))
    assert (st.d2h_bytes, st.d2h_copies) == (4000, 1)
    # outside a stats scope nothing is attributed anywhere
    Column(jnp.arange(8, dtype=jnp.int32), Domain.INT).take(np.arange(2))
    assert (st.d2h_bytes, st.d2h_copies) == (4000, 1)


def test_transfer_counted_on_a_pool_thread(clean_trace):
    clean_trace.setenv("REPRO_POOL_WORKERS", "2")
    schedule.reset_pool()
    cols = [Column(jnp.full(100 + i, i, jnp.float32), Domain.FLOAT)
            for i in range(6)]
    st = ExecStats()
    with schedule.stats_scope(st):
        schedule.dispatch_blocks(lambda c: c.take(np.arange(3)), cols)
    assert st.d2h_copies == 6
    assert st.d2h_bytes == sum(4 * (100 + i) for i in range(6))


def test_transfer_counters_exact_under_contention(clean_trace):
    """More pool workers than cores bump one teed scope (global, tenant,
    node tally) with a short switch interval: a lost update shows."""
    clean_trace.setenv("REPRO_POOL_WORKERS", "8")
    schedule.reset_pool()
    arrays = [jnp.full(64 + i % 7, i, jnp.int32) for i in range(400)]
    host = np.ones(10, np.int8)
    targets = (ExecStats(), ExecStats(), ExecStats())
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with schedule.stats_scope(StatsTee(*targets)):
            schedule.dispatch_blocks(
                lambda a: (to_host(a), note_h2d(host)), arrays)
    finally:
        sys.setswitchinterval(old)
    for st in targets:
        assert st.d2h_bytes == sum(a.nbytes for a in arrays)
        assert st.d2h_copies == len(arrays)
        assert st.h2d_bytes == host.nbytes * len(arrays)


def test_host_column_into_segment_reduce_multi_counts_h2d():
    vals = np.arange(256, dtype=np.float32)
    codes = (np.arange(256) % 4).astype(np.int32)
    st = ExecStats()
    with schedule.stats_scope(st):
        out = kops.segment_reduce_multi([vals, vals], [None, None], codes,
                                        bases=["sum", "count"],
                                        num_segments=4)
        # each host array once, though the value column is passed twice
        assert st.h2d_bytes == vals.nbytes + codes.nbytes
        kops.segment_reduce_multi([jnp.asarray(vals)], [None],
                                  jnp.asarray(codes), bases=["sum"],
                                  num_segments=4)
    assert st.h2d_bytes == vals.nbytes + codes.nbytes   # device operands: 0
    assert np.asarray(out[1]).tolist() == [64.0] * 4


def test_new_shape_bumps_compiles_and_records_compile_span():
    programs0, _ = compile_cache.counts()
    s = Session(mode=EvalMode.LAZY, trace=True)
    try:
        # a block length no other test uses: its programs are built here
        src = s.register_frame(_frame(6917, seed=13), row_parts=1)
        st0 = dataclasses.replace(s.stats)
        s.collect(_plan(src, name="trace_compile"))
        st1, tr = s.stats, s.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
    finally:
        s.close()
    assert st1.compiles > st0.compiles and st1.compile_ns > st0.compile_ns
    assert compile_cache.counts()[0] - programs0 >= st1.compiles - st0.compiles
    comp = [sp for sp in spans if sp.name == "compile"]
    assert len(comp) == st1.compiles - st0.compiles
    assert sum(sp.dur for sp in comp) == pytest.approx(
        st1.compile_ns - st0.compile_ns, abs=100_000 * len(comp))
    stmt = next(sp for sp in spans if sp.cat == "statement")
    for sp in comp:            # backdated on the statement's own clock
        assert sp.parent is not None
        assert stmt.t0 <= sp.t0 and sp.t0 + sp.dur <= stmt.t0 + stmt.dur


def test_tracing_off_records_nothing_but_counters_count():
    before = trace.recorded_total()
    s = Session(mode=EvalMode.LAZY)
    try:
        src = s.register_frame(_frame(400, seed=14), row_parts=4)
        st0 = dataclasses.replace(s.stats)
        assert s.collect(_plan(src, name="trace_off_counts")).nrows > 0
        st1 = s.stats
    finally:
        s.close()
    assert trace.recorded_total() == before
    assert st1.d2h_copies > st0.d2h_copies and st1.d2h_bytes > st0.d2h_bytes
    assert st1.h2d_bytes > st0.h2d_bytes


def test_transfer_counters_reach_the_tenant_stats():
    with QueryService(background_workers=2) as svc:
        busy = svc.session(mode=EvalMode.LAZY)
        idle = svc.session(mode=EvalMode.LAZY)
        src = busy.register_frame(_frame(400, seed=15), row_parts=4)
        busy.submit(_plan(src, name="trace_tenant_xfer")).result(timeout=30.0)
        for k in ("d2h_bytes", "d2h_copies", "h2d_bytes"):
            assert getattr(busy.stats, k) > 0, k
            assert getattr(idle.stats, k) == 0, k
            assert getattr(busy.stats, k) == getattr(svc.stats, k), k


# =============================================================================
# DROP-DUPLICATES / DIFFERENCE: the three steps as phase spans
# =============================================================================
DEDUP_STEPS = ("dedup:keys", "dedup:ids", "dedup:keep")


def _trips(s, n=3000, seed=16):
    rng = np.random.default_rng(seed)
    return api.from_pydict({
        "vendor": rng.integers(1, 3, n).tolist(),
        "pay": rng.choice(["card", "cash", "dispute"], n).tolist(),
        "lon": (rng.integers(0, 40, n) * 0.25 - 73.5).tolist(),
        "fare": (rng.random(n) * 50 + 2.5).tolist()}, session=s)


def _dedup(s):
    df = _trips(s)
    return df[df["fare"] > 10.0].drop_duplicates(["vendor", "pay", "lon"])


def _difference(s):
    df = _trips(s)
    return df[df["fare"] > 10.0].difference(df[df["fare"] > 30.0])


@pytest.mark.parametrize("build", [_dedup, _difference],
                         ids=["drop_duplicates", "difference"])
def test_dedup_records_its_steps_under_its_node(build):
    s = Session(mode=EvalMode.LAZY, trace=True, default_row_parts=3)
    try:
        q = build(s)
        st0 = dataclasses.replace(s.stats)
        q.collect()
        st1, tr = s.stats, s.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
        totals = tr.counter_totals(tr.last_stmt)
        assert tr.open_spans() == 0
    finally:
        s.close()
    by_id = {sp.id: sp for sp in spans}
    node = [sp for sp in spans if sp.cat == "node"
            and sp.name.endswith(("drop_duplicates", "difference"))]
    assert len(node) == 1
    steps = {}
    for sp in spans:
        if sp.name in DEDUP_STEPS:
            assert sp.cat == "phase" and sp.parent == node[0].id, sp.name
            steps.setdefault(sp.name, []).append(sp)
    assert sorted(steps) == sorted(DEDUP_STEPS)
    assert all(len(v) == 1 for v in steps.values())
    keys, ids, keep = (steps[n][0] for n in DEDUP_STEPS)
    assert keys.t0 + keys.dur <= ids.t0 and ids.t0 + ids.dur <= keep.t0
    # the key-extraction pool round (with the absorbed filter) runs in chunks
    # under dedup:keys, the keep-mask filter in chunks under dedup:keep
    chunks = [sp for sp in spans if sp.cat == "task"]
    under = {by_id[by_id[c.parent].parent].name for c in chunks}
    assert {"dedup:keys", "dedup:keep"} <= under
    for sp in spans:
        if sp.name == "stage:select":
            assert by_id[by_id[by_id[sp.parent].parent].parent] is keys
    # the span counter deltas still sum exactly to the statement's ExecStats
    for k in _DELTA_KEYS:
        assert totals.get(k, 0) == getattr(st1, k) - getattr(st0, k), k
    assert st1.d2h_copies > st0.d2h_copies
    assert st1.dedup_blocks > st0.dedup_blocks


def test_untraced_dedup_records_nothing():
    before = trace.recorded_total()
    s = Session(mode=EvalMode.LAZY, default_row_parts=3)
    try:
        assert _dedup(s).collect().nrows > 0
        assert _difference(s).collect().nrows > 0
        assert s.stats.dedup_blocks > 0
    finally:
        s.close()
    assert trace.recorded_total() == before
