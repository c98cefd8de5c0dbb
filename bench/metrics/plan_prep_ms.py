"""Plan preparation (rewrite and fusion, ``executor.py`` / ``rewrite.py``):
milliseconds of ``ExecStats.plan_prep_ns`` per statement of the window."""


def read(w):
    return w.stats.get("plan_prep_ns", 0) / 1e6 / w.statements if w.statements else None
