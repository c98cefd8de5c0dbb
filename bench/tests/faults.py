"""Faults planted under the timed path, for the check's own tests: each must
turn a run's ``correct`` false."""
from __future__ import annotations

import contextlib

import numpy as np

from bench import harness, readings

ROWS = 6000
SECONDS = 0.3


def run(cell, seed=11):
    """One run of ``cell`` on the CPU at test size, its chip check skipped."""
    return harness.run_cell(cell, seed, SECONDS, False, rows=ROWS,
                            require_chip=False, log=lambda *a: None)


def sound_and_control(cell, seed=12):
    """(program numbers, control numbers, limits) of one run."""
    r = readings.readings(cell, [seed], SECONDS, rows=ROWS, require_chip=False,
                          log=lambda *a: None)[0]
    return r["program"], r["control"], r["limits"]


def over(numbers, limits):
    return sorted(n for n, v in numbers.items() if v > limits[n])


@contextlib.contextmanager
def half_batch(monkeypatch):
    """The groupby partial program sees only the first half of each block's
    rows: half the batch left out, every statistic (the means too) taken over
    the rest."""
    from repro.kernels import ops
    prog = ops._segment_reduce_multi_prog

    def half(vals, valids, codes, **kw):
        h = codes.shape[0] // 2
        return prog([v[:h] for v in vals],
                    [None if m is None else m[:h] for m in valids], codes[:h], **kw)

    monkeypatch.setattr(ops, "_segment_reduce_multi_prog", half)
    yield


@contextlib.contextmanager
def altered_partial(monkeypatch):
    """One answer altered where it is produced: the first statistic of the
    first group of every partial aggregate is off by one."""
    from repro.kernels import ops
    prog = ops._segment_reduce_multi_prog

    def altered(*a, **kw):
        out = list(prog(*a, **kw))
        out[0] = out[0].at[0].add(1.0)
        return tuple(out)

    monkeypatch.setattr(ops, "_segment_reduce_multi_prog", altered)
    yield


@contextlib.contextmanager
def altered_take(monkeypatch):
    """One answer altered where it is produced: every host row gather of a
    float column returns its first value off by one."""
    from repro.core import frame
    take = frame.Column.take

    def altered(self, idx):
        c = take(self, idx)
        if c.data.dtype.kind == "f" and c.data.shape[0]:
            data = np.array(c.data)
            data[0] += 1.0
            c = frame.Column(data, c.domain, c.mask, c.dictionary)
        return c

    monkeypatch.setattr(frame.Column, "take", altered)
    yield
