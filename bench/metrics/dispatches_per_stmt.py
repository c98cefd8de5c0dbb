"""Dispatch (``schedule.py``): pool dispatches per statement of the window,
from ``ExecStats.dispatches``."""


def read(w):
    return w.stats.get("dispatches", 0) / w.statements if w.statements else None
