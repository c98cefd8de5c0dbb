"""The reduction from trace, spans and counters to per-layer metrics, on two
small cuts of a chip trace (``fixtures/trace_cut.json``: one around a groupby
partial program, one around window scans), and the byte counts behind the
roofline on hand-worked shapes."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, peaks, xtrace
from bench.metrics import kernel_bytes

FIXTURE = Path(__file__).parent / "fixtures" / "trace_cut.json"


CUTS = {"groupby_partial": "_segment_reduce_multi_prog", "window_scan": "window_scan"}


def _trace(cut):
    doc = json.loads(FIXTURE.read_text())["cuts"][cut]
    return xtrace.DeviceTrace.from_json(doc)


def _busy_ns(events, window):
    """Busy time by a sweep over +1/-1 edges: independent of DeviceTrace."""
    w0, w1 = window
    edges = sorted([(max(a, w0), 1) for a, b, _ in events if min(b, w1) > max(a, w0)]
                   + [(min(b, w1), -1) for a, b, _ in events if min(b, w1) > max(a, w0)],
                   key=lambda e: (e[0], -e[1]))
    busy, depth, since = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_busy_idle_and_gaps_match_an_independent_sweep(cut):
    tr = _trace(cut)
    assert tr.ops and tr.programs
    busy = _busy_ns(tr.ops, tr.window)
    assert tr.busy_s() == pytest.approx(busy / 1e9)
    gaps = tr.gaps()
    assert sum(b - a for a, b in gaps) + busy == tr.window[1] - tr.window[0]
    assert all(b > a for a, b in gaps)
    share = harness.metric("device_idle_share").read(SimpleNamespace(device=tr))
    assert 0.0 < share < 100.0
    assert share == pytest.approx(100.0 * (1 - busy / (tr.window[1] - tr.window[0])))


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_program_time_sums_matching_modules_inside_the_window(cut):
    tr = _trace(cut)
    w0, w1 = tr.window
    for name in CUTS.values():
        want = sum(min(b, w1) - max(a, w0) for a, b, n in tr.programs
                   if name in n and min(b, w1) > max(a, w0))
        assert tr.program_s(name) == pytest.approx(want / 1e9)
    assert tr.program_s(CUTS[cut]) > 0


def test_groupby_partial_bytes_by_hand():
    m = 1000
    a, b = ((m,), "float32", 1), ((m,), "int32", 2)
    mask, codes = ((m,), "bool", 3), ((m,), "int32", 4)
    call = (([a, a, b], [None, mask, mask], codes),
            {"bases": ("sum", "count", "max"), "num_segments": 4, "presence": True,
             "pallas": True})
    # a and b once (4 B/row each), the mask once (1 B/row), the codes
    # (4 B/row), and 4 groups x (3 statistics + presence) x 4 B written
    assert kernel_bytes.groupby_partial(call) == 4000 + 4000 + 1000 + 4000 + 4 * 4 * 4


def test_roofline_reader_on_the_fixture():
    m = 2_000_000
    calls = {"_segment_reduce_multi_prog": [
        (([((m,), "float32", 1)], [None], ((m,), "int32", 2)),
         {"bases": ("sum",), "num_segments": 8, "presence": False, "pallas": True})]}
    bw = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    gb = _trace("groupby_partial")
    w = SimpleNamespace(device=gb, kernels=calls, device_kind="TPU v5 lite")
    want = 100 * (8 * m + 32) / bw / gb.program_s("_segment_reduce_multi_prog")
    assert harness.metric("groupby_partial_roofline").read(w) == pytest.approx(want)
    # a cut that holds no groupby program: the reader finds nothing
    w.device = _trace("window_scan")
    assert harness.metric("groupby_partial_roofline").read(w) is None
    w.device, w.kernels = gb, {"_segment_reduce_multi_prog": []}
    assert harness.metric("groupby_partial_roofline").read(w) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_counter_readers():
    w = SimpleNamespace(statements=4, compiles=(2, 1), setup_compiles=(273, 270),
                        stats={"plan_prep_ns": 8_000_000, "dispatches": 10,
                               "node_wall_ns": 20_000_000})
    assert harness.metric("plan_prep_ms").read(w) == pytest.approx(2.0)
    assert harness.metric("dispatches_per_stmt").read(w) == pytest.approx(2.5)
    assert harness.metric("node_wall_ms").read(w) == pytest.approx(5.0)
    assert harness.metric("compiles_in_window").read(w) == 2.0
    assert harness.metric("setup_programs").read(w) == 273.0


def test_breakdown_attributes_idle_time_to_the_innermost_span():
    tr = xtrace.DeviceTrace(ops=[(100, 200, "a"), (400, 500, "b")],
                            programs=[(100, 200, "jit_p"), (400, 500, "jit_q")],
                            window=(0, 1000), offset_ns=0)
    Span = SimpleNamespace
    spans = [Span(t0=150, dur=400, name="eval:groupby"),
             Span(t0=250, dur=100, name="plan_prep")]
    out = xtrace.breakdown(tr, spans)
    idle = dict(out["idle_gaps"])
    # gaps: [0,100) mid 50 -> none; [200,400) mid 300 -> plan_prep;
    # [500,1000) mid 750 -> none
    assert idle == pytest.approx({"client": 600e-9, "plan_prep": 200e-9})
    assert dict(out["device_ops"]) == pytest.approx({"jit_p": 100e-9, "jit_q": 100e-9})
