"""``sweep_window_share`` reads the window path's share of the sweep's rows
from the program's counters, and nothing from a program without them."""

import pytest

from bench import trace
from bench.harness import Bench, Context
from repro.core import telemetry


def _context():
    red = trace.Reduction(window_s=1.0, busy_s=1.0, by_name={}, by_kind={},
                          gaps=[])
    return Context(params={}, reduction=red, device_kind="cpu", seed=1)


@pytest.mark.parametrize("counts, value", [
    ({"sweep_slots": 4320.0, "sweep_candidates": 58, "sweep_rows": 512,
      "sweep_window_rows": 384}, 75.0),
    ({"sweep_slots": 4320.0, "sweep_candidates": 58, "sweep_rows": 512,
      "sweep_window_rows": 0}, 0.0),
    ({"sweep_slots": 4320, "sweep_candidates": 58}, None),   # parent's
    ({"sweep_slots": 0.0, "sweep_candidates": 0, "sweep_rows": 0,
      "sweep_window_rows": 0}, None),                        # no sweep
])
def test_sweep_window_share_reads_the_counters(counts, value, monkeypatch):
    monkeypatch.setattr(telemetry, "_last_step",
                        telemetry.StepRecord(counts, 1.0))
    got = Bench().module("metrics", "sweep_window_share").read(_context())
    assert got == (pytest.approx(value) if value is not None else None)


def test_sweep_window_share_without_a_checked_step(monkeypatch):
    monkeypatch.setattr(telemetry, "_last_step", None)
    assert Bench().module("metrics", "sweep_window_share").read(
        _context()) is None
