"""Exchange (``shuffle.py``): milliseconds per statement in the sort and
join exchanges, the summed durations of the window's
``sort:``/``fused_sort:``/``join:``/``fused_join:`` phase spans of the
``exchange``, ``local`` and ``gather`` steps (``:bucketize`` lies inside
``:exchange`` and is not added again).  None where the window has no
statement or no such span."""

NAMES = frozenset(f"{op}:{step}" for op in ("sort", "fused_sort", "join", "fused_join")
                  for step in ("exchange", "local", "gather"))


def read(w):
    ns = [s.dur for s in w.spans if s.name in NAMES]
    return sum(ns) / 1e6 / w.statements if w.statements and ns else None
