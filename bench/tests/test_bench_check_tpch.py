"""The check of ``tpch_q1_q6``: a sound run passes, the control (the
reference in bfloat16 in the program's place) fails, and so does each fault
the cell can have."""
from __future__ import annotations

from bench.tests import faults

CELL = "tpch_q1_q6"


def test_sound_run_passes_and_control_fails():
    program, control, limits = faults.sound_and_control(CELL)
    assert faults.over(program, limits) == []
    assert faults.over(control, limits) != []


def test_half_batch_fails(monkeypatch):
    with faults.half_batch(monkeypatch):
        assert not faults.run(CELL)["correct"]


def test_altered_answer_fails(monkeypatch):
    with faults.altered_partial(monkeypatch):
        assert not faults.run(CELL)["correct"]
