"""TPC-H LINEITEM (Specification 3.0.1, clause 4.2.3), vectorised.

The order structure (1 to 7 lines per order, sparse order keys, order
dates) and the three columns that decide every filter of the Q1/Q6 mix
(``l_shipdate``, ``l_discount``, ``l_quantity``) come from the
configuration's fixed ``size_seed``: every run seed then filters to the same
lengths, so the engine compiles the same programs.  The run seed draws
everything else: parts and suppliers (and so the prices), tax, commit and
receipt dates (and so the return flags), ship instructions, modes and
comments.
"""
from __future__ import annotations

import numpy as np

from bench.tables import Table, stream

STARTDATE = 8035            # 1992-01-01, days since 1970-01-01
ENDDATE = 10591             # 1998-12-31
CURRENTDATE = 9298          # 1995-06-17
SUPPLIERS = 10_000          # SF 1
PARTS = 200_000             # SF 1
COMMENTS = 10_000           # size of the comment pool
SHIPINSTRUCT = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
SHIPMODE = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
WORDS = ("furiously", "sly", "careful", "blithe", "quick", "fluffy", "slow",
         "quiet", "ruthless", "thin", "close", "dogged", "daring", "brave",
         "stealthy", "permanent", "enticing", "idle", "busy", "regular",
         "final", "ironic", "even", "bold", "silent", "packages", "requests",
         "accounts", "deposits", "foxes", "ideas", "theodolites", "pinto",
         "beans", "instructions", "dependencies", "excuses", "platelets",
         "asymptotes", "courts", "dolphins", "sleep", "wake", "are", "cajole",
         "haggle", "nag", "use", "boost", "affix", "detect", "integrate",
         "about", "above", "according", "to", "across", "after", "against")


def _order_structure(rows: int, size_seed: int):
    """Per line: (order index, line number, order date), exactly ``rows``."""
    rng = stream(size_seed, 1)
    n_orders = rows // 4 + 8           # 4 lines per order on average
    lines = rng.integers(1, 8, n_orders)
    while lines.sum() < rows:          # only at tiny sizes
        lines = np.concatenate([lines, rng.integers(1, 8, n_orders)])
    ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, rows) + 1)
    lines = lines[:n_orders].copy()
    lines[-1] -= int(ends[n_orders - 1] - rows)
    order = np.repeat(np.arange(n_orders, dtype=np.int64), lines)
    starts = np.cumsum(lines) - lines
    linenumber = np.arange(rows, dtype=np.int64) - starts[order] + 1
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders)
    return order, linenumber, odate[order]


def make(rows: int, seed: int, config: dict) -> Table:
    order, linenumber, orderdate = _order_structure(rows, config["size_seed"])
    fixed = stream(config["size_seed"], 2)
    shipdate = orderdate + fixed.integers(1, 122, rows)
    discount = fixed.integers(0, 11, rows) / 100.0
    quantity = fixed.integers(1, 51, rows).astype(np.float64)

    rng = stream(seed, 1)
    partkey = rng.integers(1, PARTS + 1, rows)
    i = rng.integers(0, 4, rows)
    suppkey = (partkey + i * (SUPPLIERS // 4 + (partkey - 1) // SUPPLIERS)) % SUPPLIERS + 1
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0
    tax = rng.integers(0, 9, rows) / 100.0
    commitdate = orderdate + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    # returnflag codes index ("A", "N", "R"): R or A when received by the
    # current date, else N; linestatus ("F", "O"): O when shipped after it
    returned = receiptdate <= CURRENTDATE
    returnflag = np.where(returned, np.where(rng.random(rows) < 0.5, 2, 0), 1)
    linestatus = (shipdate > CURRENTDATE).astype(np.int32)

    words = np.asarray(WORDS, dtype=object)
    pool_rng = stream(seed, 2)
    pool = tuple(f"{' '.join(words[pool_rng.integers(0, len(WORDS), k)])[:37]} {j}"
                 for j, k in enumerate(pool_rng.integers(2, 7, COMMENTS)))

    f32 = np.float32
    i32 = np.int32
    data = {
        "l_orderkey": ((order // 8) * 32 + order % 8 + 1).astype(i32),
        "l_partkey": partkey.astype(i32),
        "l_suppkey": suppkey.astype(i32),
        "l_linenumber": linenumber.astype(i32),
        "l_quantity": quantity.astype(f32),
        "l_extendedprice": (quantity * retail).astype(f32),
        "l_discount": discount.astype(f32),
        "l_tax": tax.astype(f32),
        "l_returnflag": returnflag.astype(i32),
        "l_linestatus": linestatus,
        "l_shipdate": shipdate.astype(i32),
        "l_commitdate": commitdate.astype(i32),
        "l_receiptdate": receiptdate.astype(i32),
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), rows, dtype=i32),
        "l_shipmode": rng.integers(0, len(SHIPMODE), rows, dtype=i32),
        "l_comment": rng.integers(0, COMMENTS, rows, dtype=i32),
    }
    labels = {"l_returnflag": ("A", "N", "R"), "l_linestatus": ("F", "O"),
              "l_shipinstruct": SHIPINSTRUCT, "l_shipmode": SHIPMODE,
              "l_comment": pool}
    return Table(data, labels=labels, text=frozenset({"l_comment"}))
