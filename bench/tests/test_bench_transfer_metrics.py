"""The readers of the groupby key step and of the host-device transfer
counters, on a synthetic window; and on a window from an engine that has
neither (the parent of the change that added them): no number, no error."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness

Span = SimpleNamespace
READERS = ("groupby_keys_ms", "d2h_mb_per_stmt", "h2d_mb_per_stmt",
           "d2h_copies_per_stmt")


def _window(statements=4, stats=None, spans=()):
    return harness.Window(statements=statements, stats=stats or {}, spans=list(spans))


def test_groupby_keys_ms_sums_only_its_spans():
    spans = [Span(name="groupby:keys", dur=3_000_000),
             Span(name="groupby:keys", dur=5_000_000),
             Span(name="groupby:combine", dur=7_000_000),
             Span(name="eval:fused_groupby", dur=40_000_000)]
    w = _window(statements=4, spans=spans)
    assert harness.metric("groupby_keys_ms").read(w) == pytest.approx(2.0)


def test_transfer_readers_divide_the_window_delta():
    w = _window(statements=4, stats={"d2h_bytes": 12_000_000, "h2d_bytes": 600_000_000,
                                     "d2h_copies": 90, "dispatches": 7})
    assert harness.metric("d2h_mb_per_stmt").read(w) == pytest.approx(3.0)
    assert harness.metric("h2d_mb_per_stmt").read(w) == pytest.approx(150.0)
    assert harness.metric("d2h_copies_per_stmt").read(w) == pytest.approx(22.5)


def test_zero_is_a_reading():
    w = _window(stats={"d2h_bytes": 0, "h2d_bytes": 0, "d2h_copies": 0},
                spans=[Span(name="groupby:keys", dur=0)])
    for name in READERS:
        assert harness.metric(name).read(w) == 0.0, name


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_none(name):
    # no statement in the window
    assert harness.metric(name).read(_window(statements=0, stats={
        "d2h_bytes": 5, "h2d_bytes": 5, "d2h_copies": 5},
        spans=[Span(name="groupby:keys", dur=5)])) is None
    # an engine without the counters and spans
    old = _window(stats={"node_wall_ns": 10, "dispatches": 3},
                  spans=[Span(name="eval:fused_groupby", dur=10)])
    assert harness.metric(name).read(old) is None


def test_every_reader_is_listed_for_the_cell():
    listed = {m["name"] for m in harness.cell_metrics(harness.benchmark(), "tpch_q1_q6",
                                                      "per_layer")}
    assert set(READERS) <= listed
