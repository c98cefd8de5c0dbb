"""NYC TLC yellow taxi trips of January 2015 (``yellow_tripdata_2015-01``,
the 2015 Yellow Taxi data dictionary), vectorised: the 19 published columns
in the published order.

The fixed ``size_seed`` stream draws the rate code, the distance and every
money column, and so the payment type (a cash trip has no tip): the filters
of the ``taxi_shuffle`` mix cut ``fare_amount``, ``total_amount`` and
``trip_distance``, and the merge matches ``RateCodeID``, so every run seed
filters and joins to the same lengths.  The run seed draws the vendor, the
times, the passenger count, the coordinates and the store-and-forward flag.
"""
from __future__ import annotations

import numpy as np

from bench.tables import Table, stream

JAN_2015 = 1420070400           # 2015-01-01 00:00:00 UTC, epoch seconds
MONTH_S = 31 * 86400
RATE_CODES = np.array([1, 2, 3, 4, 5, 6, 99])
RATE_P = np.array([0.97235, 0.0205, 0.0017, 0.0004, 0.0048, 0.00005, 0.0002])
PAYMENT = ("Credit card", "Cash", "No charge", "Dispute", "Unknown", "Voided trip")
PAYMENT_P = np.array([0.61, 0.38, 0.007, 0.002, 0.0005, 0.0005])
PASSENGERS_P = np.array([0.0004, 0.705, 0.142, 0.041, 0.020, 0.057, 0.034,
                         0.0002, 0.0002, 0.0002])
MISSING_GPS = 0.02
CENTER = (-73.980, 40.755)      # midtown Manhattan (lon, lat)
SPREAD = (0.018, 0.025)         # degrees
COLUMNS = ("VendorID", "tpep_pickup_datetime", "tpep_dropoff_datetime",
           "passenger_count", "trip_distance", "pickup_longitude",
           "pickup_latitude", "RateCodeID", "store_and_fwd_flag",
           "dropoff_longitude", "dropoff_latitude", "payment_type",
           "fare_amount", "extra", "mta_tax", "tip_amount", "tolls_amount",
           "improvement_surcharge", "total_amount")
MONEY = ("fare_amount", "extra", "mta_tax", "tip_amount", "tolls_amount",
         "improvement_surcharge")


def _choice(rng, values, p, n):
    """``n`` draws of ``values`` with probabilities ``p`` (normalised)."""
    cdf = np.cumsum(p) / np.sum(p)
    return np.asarray(values)[np.minimum(np.searchsorted(cdf, rng.random(n)),
                                         len(cdf) - 1)]


def _cents(x):
    return np.round(np.asarray(x, np.float64) * 100.0) / 100.0


def _fixed(rows: int, size_seed: int) -> dict:
    """Rate code, distance, money and payment type: the same for every seed."""
    rng = stream(size_seed, 1)
    rate = _choice(rng, RATE_CODES, RATE_P, rows)
    # distance: mostly short city trips, a long airport tail, some 0.0
    dist = np.exp(rng.normal(np.log(1.6), 0.75, rows))
    far = (rng.random(rows) < 0.05) | (rate == 2) | (rate == 3)
    dist = np.where(far, np.exp(rng.normal(np.log(14.0), 0.35, rows)), dist)
    dist = np.where(rng.random(rows) < 0.006, 0.0, _cents(dist))
    # the 2015 meter: $2.50 + $2.50 a mile + $0.50 a slow minute, in $0.50
    slow = rng.exponential(3.0, rows)
    meter = np.round((2.5 + 2.5 * dist + 0.5 * slow) * 2.0) / 2.0
    fare = np.select([rate == 2, rate == 3, rate == 4, rate == 5],
                     [np.full(rows, 52.0), meter + 17.5,
                      np.round((2.5 + 5.0 * dist) * 2.0) / 2.0,
                      np.round(rng.uniform(10.0, 150.0, rows))], meter)
    pay = _choice(rng, np.arange(len(PAYMENT)), PAYMENT_P, rows)
    extra = _choice(rng, [0.0, 0.5, 1.0], [0.5, 0.35, 0.15], rows)
    mta = np.where(rng.random(rows) < 0.995, 0.5, 0.0)
    surcharge = np.where(rng.random(rows) < 0.9995, 0.3, 0.0)
    tolls = np.where(rng.random(rows) < 0.95, 0.0,
                     _choice(rng, [5.33, 2.54, 10.66, 11.75], [0.8, 0.1, 0.05, 0.05], rows))
    # card trips tip about a fifth of the fare (a tenth tip nothing); every
    # other payment type records no tip
    share = np.exp(rng.normal(np.log(0.2), 0.3, rows))
    tipped = (pay == 0) & (rng.random(rows) >= 0.1)
    tip = np.where(tipped, _cents(fare * share), 0.0)
    money = {"fare_amount": fare, "extra": extra, "mta_tax": mta,
             "tip_amount": tip, "tolls_amount": tolls,
             "improvement_surcharge": surcharge}
    cents = sum(np.round(money[n] * 100.0).astype(np.int64) for n in MONEY)
    return dict(money, RateCodeID=rate, trip_distance=dist, payment_type=pay,
                total_amount=cents / 100.0)


def make(rows: int, seed: int, config: dict) -> Table:
    fx = _fixed(rows, config["size_seed"])
    rng = stream(seed, 1)
    vendor = np.where(rng.random(rows) < 0.48, 1, 2)
    pickup = JAN_2015 + rng.integers(0, MONTH_S, rows)
    # about 11 mph through the city, at least a minute
    mph = np.exp(rng.normal(np.log(11.0), 0.35, rows))
    dropoff = pickup + 60 + np.round(fx["trip_distance"] / mph * 3600.0).astype(np.int64)
    passengers = _choice(rng, np.arange(10), PASSENGERS_P, rows)
    lon = rng.normal(CENTER[0], SPREAD[0], rows)
    lat = rng.normal(CENTER[1], SPREAD[1], rows)
    heading = rng.uniform(0.0, 2 * np.pi, rows)
    dlat = fx["trip_distance"] / 69.0 * np.sin(heading)
    dlon = fx["trip_distance"] / 52.4 * np.cos(heading)   # miles per degree at 40.75 N
    missing = rng.random(rows) < MISSING_GPS
    flag = (rng.random(rows) < 0.01).astype(np.int32)

    f32, i32 = np.float32, np.int32

    def coord(v):
        return np.where(missing, 0.0, v).astype(f32)

    data = {
        "VendorID": vendor.astype(i32),
        "tpep_pickup_datetime": pickup.astype(i32),
        "tpep_dropoff_datetime": dropoff.astype(i32),
        "passenger_count": passengers.astype(i32),
        "trip_distance": fx["trip_distance"].astype(f32),
        "pickup_longitude": coord(lon),
        "pickup_latitude": coord(lat),
        "RateCodeID": fx["RateCodeID"].astype(i32),
        "store_and_fwd_flag": flag,
        "dropoff_longitude": coord(lon + dlon),
        "dropoff_latitude": coord(lat + dlat),
        "payment_type": fx["payment_type"].astype(i32),
    }
    for name in MONEY + ("total_amount",):
        data[name] = fx[name].astype(f32)
    data = {name: data[name] for name in COLUMNS}
    labels = {"store_and_fwd_flag": ("N", "Y"), "payment_type": PAYMENT}
    return Table(data, labels=labels)
