"""The check of ``taxi_shuffle``: a sound run passes, the control (the
reference in bfloat16 in the program's place) fails on every operator, and
so does each fault the cell can have: two tied rows swapped in a sorted
answer, one row dropped from a merge, one duplicate kept by a dedup.  And
the cell's two per-layer readers on hand-made windows."""
from __future__ import annotations

import contextlib
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.tests import faults

CELL = "taxi_shuffle"
NUMBERS = ("dedup_bad", "merge_bad", "sort_bad")


def test_sound_run_passes_and_control_fails():
    program, control, limits = faults.sound_and_control(CELL)
    assert sorted(program) == list(NUMBERS)
    assert faults.over(program, limits) == []
    # the bfloat16 control reorders ties, moves values and merges keys
    assert faults.over(control, limits) == list(NUMBERS)


@contextlib.contextmanager
def swapped_ties(monkeypatch):
    """Each local sort of the exchange swaps its first two tied rows."""
    from repro.core import shuffle
    lex = shuffle._lex_perm

    def swapped(keys):
        perm = np.array(lex(keys))
        k = np.stack([np.asarray(c)[perm] for c in keys], axis=1)
        tied = np.nonzero((k[1:] == k[:-1]).all(axis=1))[0]
        if tied.size:
            i = tied[0]
            perm[[i, i + 1]] = perm[[i + 1, i]]
        return perm

    monkeypatch.setattr(shuffle, "_lex_perm", swapped)
    yield


@contextlib.contextmanager
def dropped_match(monkeypatch):
    """Each join loses the last row of its matched pairs."""
    from repro.core import shuffle
    merge = shuffle._merge_join_results

    def dropped(*a, **kw):
        return tuple(None if v is None else v[:-1] for v in merge(*a, **kw))

    monkeypatch.setattr(shuffle, "_merge_join_results", dropped)
    yield


@contextlib.contextmanager
def kept_duplicate(monkeypatch):
    """Each dedup keeps the first row that its keep masks drop."""
    from repro.core import physical
    apply = physical._apply_keep_blocks

    def kept(blocks, keeps, proj):
        keeps = [np.array(k) for k in keeps]
        for k in keeps:
            off = np.nonzero(~k)[0]
            if off.size:
                k[off[0]] = True
                break
        return apply(blocks, keeps, proj)

    monkeypatch.setattr(physical, "_apply_keep_blocks", kept)
    yield


@pytest.mark.parametrize("fault,number", [
    (swapped_ties, "sort_bad"), (dropped_match, "merge_bad"),
    (kept_duplicate, "dedup_bad")], ids=["sort", "merge", "dedup"])
def test_planted_fault_fails(monkeypatch, fault, number):
    with fault(monkeypatch):
        out = faults.run(CELL)
    assert not out["correct"]
    assert out["checks"][number]["value"] > 0
    assert all(c["value"] == 0 for n, c in out["checks"].items() if n != number)


def _span(name, dur):
    return SimpleNamespace(name=name, dur=dur)


def test_shuffle_ms_sums_the_exchange_steps_once():
    spans = [_span("sort:exchange", 5_000_000),
             _span("sort:bucketize", 3_000_000),       # inside sort:exchange
             _span("sort:local", 2_000_000), _span("sort:gather", 1_000_000),
             _span("fused_sort:exchange", 4_000_000),
             _span("fused_join:local", 6_000_000), _span("join:gather", 2_000_000),
             _span("dispatch:sort:exchange", 9_000_000),
             _span("chunk:join:local", 9_000_000), _span("dedup:keys", 9_000_000)]
    read = harness.metric("shuffle_ms").read
    assert read(SimpleNamespace(statements=4, spans=spans)) == pytest.approx(20.0 / 4)
    assert read(SimpleNamespace(statements=4, spans=spans[-3:])) is None
    assert read(SimpleNamespace(statements=0, spans=spans)) is None


def test_dedup_ms_sums_its_three_steps():
    spans = [_span("dedup:keys", 4_000_000), _span("dedup:ids", 2_000_000),
             _span("keys:unique", 1_000_000),           # inside dedup:ids
             _span("dedup:keep", 3_000_000), _span("stage:select", 7_000_000),
             _span("sort:exchange", 5_000_000)]
    read = harness.metric("dedup_ms").read
    assert read(SimpleNamespace(statements=3, spans=spans)) == pytest.approx(9.0 / 3)
    assert read(SimpleNamespace(statements=3, spans=spans[-2:])) is None
    assert read(SimpleNamespace(statements=0, spans=spans)) is None
