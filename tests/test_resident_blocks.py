"""Device-resident source blocks.

Registering a frame whose columns are device arrays row-partitions it on
the device (``Frame.split_rows``): every block's columns and masks stay
``jax.Array`` with the values, labels and block edges of the host split,
so a statement sends none of them again.  A host frame still splits on the
host.  A masked groupby over resident blocks answers bit for bit as over
host blocks, sends only its host-built group codes, and counts the
resident columns it read in ``ExecStats.resident_cols``; a row take reads
each resident column to the host once, in its first statement.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EvalMode, QueryService, Session, api
from repro.core.dtypes import Domain
from repro.core.frame import Column, Frame
from repro.core.labels import RangeLabels, labels_from_values
from repro.core.partition import PartitionedFrame
from repro.core.schedule import pool_width

from test_groupby_masked import _assert_same, _q1, _q6

NAMES = ["flag", "status", "qty", "price", "disc", "tax", "ship"]


def _table(n=3001, seed=3, device=True, masked=False):
    """A lineitem-shaped frame, its columns on the device or the host;
    ``masked`` gives ``price`` and ``qty`` a validity mask."""
    rng = np.random.default_rng(seed)
    put = jnp.asarray if device else np.asarray
    price_ok = rng.random(n) > 0.1 if masked else None
    qty_ok = rng.random(n) > 0.05 if masked else None

    def col(values, dom, ok=None, table=None):
        return Column(put(values), dom, None if ok is None else put(ok), table)

    cols = [
        col(rng.integers(0, 3, n).astype(np.int32), Domain.CATEGORY,
            table=("A", "N", "R")),
        col(rng.integers(0, 2, n).astype(np.int32), Domain.CATEGORY,
            table=("F", "O")),
        col(rng.integers(1, 51, n).astype(np.int32), Domain.INT, qty_ok),
        col((rng.random(n) * 100 + 0.5).astype(np.float32), Domain.FLOAT,
            price_ok),
        col((rng.integers(0, 11, n) / 100 + 0.001).astype(np.float32),
            Domain.FLOAT),
        col((rng.integers(0, 9, n) / 100 + 0.001).astype(np.float32),
            Domain.FLOAT),
        col(rng.integers(0, 400, n).astype(np.int32), Domain.INT),
    ]
    return Frame(cols, RangeLabels(n), labels_from_values(NAMES))


def _blocks(frame, parts):
    return [row[0] for row in PartitionedFrame.from_frame(frame, parts).parts]


def _arrays(block):
    return [a for c in block.columns for a in (c.data, c.mask) if a is not None]


GRIDS = {"one": lambda w: 1, "pool": lambda w: w, "four_pools": lambda w: 4 * w}


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("grid", list(GRIDS))
def test_device_frame_splits_into_resident_blocks(grid, masked):
    parts = GRIDS[grid](pool_width())
    dev = _blocks(_table(masked=masked), parts)
    host = _blocks(_table(device=False, masked=masked), parts)
    assert len(dev) == len(host) == min(parts, 3001)
    assert [b.nrows for b in dev] == [b.nrows for b in host]
    for d, h in zip(dev, host):
        assert all(isinstance(a, jax.Array) for a in _arrays(d))
        assert d.row_labels.to_list() == h.row_labels.to_list()
        assert d.col_labels.to_list() == h.col_labels.to_list()
        for cd, ch in zip(d.columns, h.columns):
            assert cd.domain is ch.domain and cd.dictionary == ch.dictionary
            assert (cd.mask is None) == (ch.mask is None)
            np.testing.assert_array_equal(np.asarray(cd.data), ch.data)
            if cd.mask is not None:
                np.testing.assert_array_equal(np.asarray(cd.mask), ch.mask)


def test_one_block_keeps_the_registered_arrays():
    f = _table(masked=True)
    (block,) = _blocks(f, 1)
    assert all(a is b for a, b in zip(_arrays(block), _arrays(f)))


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
def test_host_frame_keeps_host_blocks(masked):
    for block in _blocks(_table(device=False, masked=masked), 3):
        assert all(isinstance(a, np.ndarray) for a in _arrays(block))


def test_each_array_keeps_its_place():
    # data and mask split apart: a device array on the device, a host one
    # on the host
    n = 1000
    f = Frame([Column(jnp.arange(n, dtype=jnp.int32), Domain.INT,
                      np.arange(n) % 3 > 0),
               Column(np.arange(n, dtype=np.float32), Domain.FLOAT,
                      jnp.asarray(np.arange(n) % 5 > 0))],
              RangeLabels(n), labels_from_values(["a", "b"]))
    blocks = _blocks(f, 4)
    for b, r0 in zip(blocks, np.cumsum([0] + [b.nrows for b in blocks])):
        rows = np.arange(r0, r0 + b.nrows)
        a, c = b.columns
        assert isinstance(a.data, jax.Array) and isinstance(a.mask, np.ndarray)
        assert isinstance(c.data, np.ndarray) and isinstance(c.mask, jax.Array)
        np.testing.assert_array_equal(np.asarray(a.data), rows)
        np.testing.assert_array_equal(a.mask, rows % 3 > 0)
        np.testing.assert_array_equal(c.data, rows.astype(np.float32))
        np.testing.assert_array_equal(np.asarray(c.mask), rows % 5 > 0)


def _statements(build, device):
    """Two statements' results and counter deltas over one registered
    table in three blocks of one shape, on the chip's path (map chains
    traced, so the second statement traces nothing)."""
    s = Session(mode=EvalMode.LAZY, default_row_parts=3, cache_budget_bytes=0)
    try:
        df = api.DataFrame(_table(3000, device=device, masked=True), session=s)
        out = []
        for _ in range(2):
            st0 = dataclasses.replace(s.stats)
            q = build(df)
            res = q.sum() if isinstance(q, api.ColumnExpr) else q.collect()
            out.append((res, {k: getattr(s.stats, k) - getattr(st0, k)
                              for k in ("h2d_bytes", "d2h_bytes",
                                        "resident_cols", "groupby_masked")}))
        return out
    finally:
        s.close()


@pytest.mark.parametrize("build, codes_bytes", [(_q1, 3000 * 4), (_q6, 0)],
                         ids=["q1", "q6"])
def test_masked_groupby_over_resident_blocks(monkeypatch, build, codes_bytes):
    monkeypatch.setenv("REPRO_JIT_UDFS", "1")
    dev = _statements(build, device=True)
    host = _statements(build, device=False)
    for (rd, dd), (rh, dh) in zip(dev, host):
        _assert_same(rd, rh)
        assert dd["groupby_masked"] == dh["groupby_masked"] == 1
        assert dd["resident_cols"] > 0 and dh["resident_cols"] == 0
    # once warm, a statement over resident blocks sends only the group
    # codes built on the host (Q6 builds its codes on the device)
    assert dev[1][1]["h2d_bytes"] == codes_bytes
    assert host[1][1]["h2d_bytes"] > codes_bytes


def test_row_take_reads_each_resident_column_once():
    f = _table(masked=True)
    src = sum(a.nbytes for a in _arrays(f))
    s = Session(mode=EvalMode.LAZY, default_row_parts=3, cache_budget_bytes=0)
    try:
        df = api.DataFrame(f, session=s)
        got = []
        for cut in (200, 300):
            st0 = s.stats.d2h_bytes
            out = df[df["ship"] < cut].sort_values("price").collect()
            got.append((out, s.stats.d2h_bytes - st0))
    finally:
        s.close()
    (first, d1), (second, d2) = got
    assert first.nrows < second.nrows
    # the second statement reads back only its own filter's keep-mask
    assert d2 < src and d1 - d2 == src


def test_factorized_groupby_decodes_keys_from_the_host_copy():
    # a float key takes the general factorization, which decodes one
    # representative row per group: from the key column's cached host
    # copy, not by a device read per group
    s = Session(mode=EvalMode.LAZY, default_row_parts=3, cache_budget_bytes=0)
    try:
        df = api.DataFrame(_table(), session=s)
        host = api.DataFrame(_table(device=False), session=s)
        copies = []
        for _ in range(2):
            st0 = dataclasses.replace(s.stats)
            out = df.groupby("price").agg({"qty": ["sum"]}).collect()
            copies.append(s.stats.d2h_copies - st0.d2h_copies)
            assert s.stats.groupby_factorized - st0.groupby_factorized == 1
        want = host.groupby("price").agg({"qty": ["sum"]}).collect()
    finally:
        s.close()
    assert out.nrows > 2000
    _assert_same(out, want)
    assert copies[0] <= 3 and copies[1] == 0


@pytest.mark.trace
def test_resident_cols_reach_node_span_and_tenant_stats():
    with QueryService(background_workers=2) as svc:
        busy = svc.session(mode=EvalMode.LAZY, trace=True)
        idle = svc.session(mode=EvalMode.LAZY)
        node = svc.register_frame(_table(), row_parts=3)
        q = _q1(api.DataFrame(session=busy, node=node))
        busy.submit(q._node).result(timeout=60.0)
        tr = busy.tracer
        spans = [sp for sp in tr.snapshot() if sp.stmt == tr.last_stmt]
        fused = [sp for sp in spans if sp.name == "eval:fused_groupby"]
        assert len(fused) == 1 and fused[0].args["resident_cols"] > 0
        assert tr.counter_totals(tr.last_stmt)["resident_cols"] \
            == busy.stats.resident_cols > 0
        assert idle.stats.resident_cols == 0
        assert svc.stats.resident_cols == busy.stats.resident_cols
