"""The rate codes of the 2015 Yellow Taxi data dictionary: ``RateCodeID``
1 to 6 and their names, the lookup table of the ``taxi_shuffle`` merge.
The same six rows for every seed."""
from __future__ import annotations

import numpy as np

from bench.tables import Table

NAMES = ("Standard rate", "JFK", "Newark", "Nassau or Westchester",
         "Negotiated fare", "Group ride")


def make(rows: int, seed: int, config: dict) -> Table:
    return Table({"RateCodeID": np.arange(1, rows + 1, dtype=np.int32),
                  "rate_code": np.arange(rows, dtype=np.int32)},
                 labels={"rate_code": NAMES})
