"""Configurations and cells resolve by name; generators are seeded and
keep the published schemas."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, tables

ROWS = 3000


def _bench():
    return harness.benchmark()


def test_every_cell_resolves_to_its_files():
    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        conf = configs[w["config"]]
        assert (harness.ROOT / conf["file"]).is_file()
        assert json.loads((harness.ROOT / conf["file"]).read_text())["name"] == w["config"]
        mix = harness.traffic(w["traffic"])
        for t in mix["tables"]:
            assert (harness.BENCH / "configs" / w["config"] / f"{t}.py").is_file()
        for stmts in mix["clients"]:
            for s in stmts:
                mod = harness.template(s["template"])
                for attr in ("SMALL", "LIMITS", "prepare", "run", "reference", "compare"):
                    assert hasattr(mod, attr), (s["template"], attr)
        for m in harness.cell_metrics(bench, w["name"], "per_layer"):
            assert callable(harness.metric(m["name"]).read)
        e2e = {m["name"] for m in harness.cell_metrics(bench, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        moved = {m["moves"] for m in harness.cell_metrics(bench, w["name"], "per_layer")}
        assert moved <= e2e


def test_config_files_state_source_reduced_assumed():
    for c in _bench()["configs"]:
        doc = tables.config(c["name"])
        assert doc["source"] == c["source"] and len(doc["source"]) <= 200
        assert doc["reduced"] == c["reduced"]
        assert doc["assumed"] and doc["guarantees"]


@pytest.mark.parametrize("config,table,columns", [
    ("tpch_sf1", "lineitem", [
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
        "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
        "l_shipmode", "l_comment"]),
])
def test_schema_and_seeding(config, table, columns):
    conf = tables.config(config)
    rows = min(ROWS, conf["tables"][table]["rows"])
    a = tables.load(config, table, rows, 2**31 + 7)
    assert list(a.data) == columns and len(columns) == conf["tables"][table]["columns"]
    for name, v in a.data.items():
        assert v.shape == (rows,) and v.dtype in (np.int32, np.float32), name
        if name in a.labels:
            assert v.min() >= 0 and v.max() < len(a.labels[name])
    for m in a.valid.values():
        assert m.dtype == bool and m.shape == (rows,)
    b = tables.load(config, table, rows, 2**31 + 7)
    for name in columns:
        np.testing.assert_array_equal(a.data[name], b.data[name])
    assert a.labels == b.labels


def test_seed_changes_data_but_not_filtered_lengths():
    """The run seed draws most columns; the columns that set filter lengths
    come from the configuration's fixed stream."""
    li = [tables.load("tpch_sf1", "lineitem", ROWS, s) for s in (1, 2)]
    for name in ("l_shipdate", "l_discount", "l_quantity", "l_orderkey"):
        np.testing.assert_array_equal(li[0].data[name], li[1].data[name])
    assert not np.array_equal(li[0].data["l_extendedprice"], li[1].data["l_extendedprice"])


def test_spec_distributions_hold():
    li = tables.load("tpch_sf1", "lineitem", 20000, 3)
    d = li.data
    assert d["l_quantity"].min() >= 1 and d["l_quantity"].max() <= 50
    assert set(np.round(d["l_discount"] * 100).astype(int)) <= set(range(11))
    assert (d["l_receiptdate"] > d["l_shipdate"]).all()
    assert d["l_linenumber"].min() == 1 and d["l_linenumber"].max() <= 7
    flag = np.asarray(li.labels["l_returnflag"])[d["l_returnflag"]]
    assert ((flag == "N") == (d["l_receiptdate"] > 9298)).all()
