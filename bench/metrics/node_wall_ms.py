"""Operators (``physical.py``): milliseconds of ``ExecStats.node_wall_ns``,
the time inside physical node programs, per statement of the window."""


def read(w):
    return w.stats.get("node_wall_ns", 0) / 1e6 / w.statements if w.statements else None
