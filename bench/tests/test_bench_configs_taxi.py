"""The ``taxi_2015_01`` generators: the dictionary's schemas, seeding, the
fixed stream behind the filtered lengths, and the dictionary's domains."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness, tables
from bench.configs.taxi_2015_01 import trips as gen
from bench.templates import _taxi

ROWS = 3000
CONFIG = "taxi_2015_01"
# the filtered and joined columns, and the money parts and payment type that
# total_amount and the cash rule tie to them
FIXED = ("fare_amount", "total_amount", "trip_distance", "RateCodeID",
         "payment_type", "tip_amount", "extra", "mta_tax", "tolls_amount",
         "improvement_surcharge")


@pytest.mark.parametrize("table,columns", [
    ("trips", list(gen.COLUMNS)),
    ("rate_codes", ["RateCodeID", "rate_code"]),
])
def test_schema_and_seeding(table, columns):
    conf = tables.config(CONFIG)
    rows = min(ROWS, conf["tables"][table]["rows"])
    a = tables.load(CONFIG, table, rows, 2**31 + 7)
    assert list(a.data) == columns and len(columns) == conf["tables"][table]["columns"]
    for name, v in a.data.items():
        assert v.shape == (rows,) and v.dtype in (np.int32, np.float32), name
        if name in a.labels:
            assert v.min() >= 0 and v.max() < len(a.labels[name])
    assert a.valid == {}
    b = tables.load(CONFIG, table, rows, 2**31 + 7)
    for name in columns:
        np.testing.assert_array_equal(a.data[name], b.data[name])
    assert a.labels == b.labels


def test_trips_columns_in_the_dictionary_order():
    assert gen.COLUMNS[:5] == ("VendorID", "tpep_pickup_datetime",
                               "tpep_dropoff_datetime", "passenger_count",
                               "trip_distance")
    assert gen.COLUMNS[-1] == "total_amount" and len(gen.COLUMNS) == 19


def test_seed_changes_data_but_not_filtered_lengths():
    """Every statement of the mix keeps the same rows for any seed; the run
    seed draws the vendor, the times, the passengers and the coordinates."""
    trips = [tables.load(CONFIG, "trips", ROWS, s) for s in (1, 2**31 + 3)]
    for name in FIXED:
        np.testing.assert_array_equal(trips[0].data[name], trips[1].data[name])
    for name in ("tpep_pickup_datetime", "passenger_count", "pickup_longitude",
                 "dropoff_latitude", "VendorID"):
        assert not np.array_equal(trips[0].data[name], trips[1].data[name]), name
    hosts = [tables.Host({"trips": t}) for t in trips]
    for stmts in harness.traffic("taxi_shuffle")["clients"]:
        for st in stmts:
            kept = [_taxi.kept(h, _taxi.prepare(h, st["params"]), False)[1]
                    for h in hosts]
            np.testing.assert_array_equal(kept[0], kept[1])
            assert 0 < len(kept[0]) < ROWS


def test_dictionary_distributions_hold():
    t = tables.load(CONFIG, "trips", 20000, 5)
    d = t.data

    def cents(v):
        return np.round(v.astype(np.float64) * 100).astype(np.int64)

    np.testing.assert_array_equal(sum(cents(d[n]) for n in gen.MONEY),
                                  cents(d["total_amount"]))
    assert (d["tpep_dropoff_datetime"] > d["tpep_pickup_datetime"]).all()
    assert d["tpep_pickup_datetime"].min() >= gen.JAN_2015
    assert d["tpep_pickup_datetime"].max() < gen.JAN_2015 + gen.MONTH_S
    assert set(np.unique(d["RateCodeID"]).tolist()) <= {1, 2, 3, 4, 5, 6, 99}
    assert (d["RateCodeID"] == 1).mean() > 0.95 and (d["RateCodeID"] == 99).any()
    assert (d["passenger_count"] == 1).mean() > 0.6
    assert d["passenger_count"].min() >= 0 and d["passenger_count"].max() <= 9
    card = np.asarray(t.labels["payment_type"])[d["payment_type"]] == "Credit card"
    assert (d["tip_amount"][~card] == 0).all() and (d["tip_amount"][card] > 0).any()
    assert (d["fare_amount"] > 0).all() and (d["trip_distance"] >= 0).all()
    gps = (d["pickup_longitude"] == 0) & (d["pickup_latitude"] == 0)
    assert 0.01 < gps.mean() < 0.03
    codes = tables.load(CONFIG, "rate_codes", 6, 5)
    np.testing.assert_array_equal(codes.data["RateCodeID"], np.arange(1, 7))
    assert np.asarray(codes.labels["rate_code"])[codes.data["rate_code"]].tolist() == [
        "Standard rate", "JFK", "Newark", "Nassau or Westchester",
        "Negotiated fare", "Group ride"]
