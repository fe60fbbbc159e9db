"""Per-phase device time, the program's host spans in the trace reduction
and the new readers (``bench/phases.py``, ``bench/trace.py``,
``repro.core.telemetry``).

``testdata/scoped_step.xplane.pb`` traces two calls of one jitted program
on the CPU: a sort under ``jax.named_scope("grid_build")``, a gather under
``jax.named_scope("neighbor_sweep")``, then a cumulative sum and a product
outside any scope. Each call sits in a ``run_call`` inside one ``window``;
in it, a ``sim.step`` span (iteration=i) holds 5 ms of sleep and the
dispatch, and a ``sim.overflow_check`` span the wait for the result and 20
ms of sleep. ``testdata/scoped_step.hlo.txt`` is the program's compiled
HLO. The expected numbers below were summed by hand from the trace's op
events (start and end in ns):

  call 1  sort.0 5398050-5439863, 5440455-26125999  gather_bitcast_fusion
          26129229-26286865  then eight unscoped ops 26291211-26688915
          (192110 + 23556 + 26963 + 2022 + 3445 + 1437 + 2291 + 94452 ns)
  call 2  sort.0 52319135-52362567, 52363160-78161393  gather_bitcast_fusion
          78164503-78326431  then the same eight 78327849-78682286
          (188617 + 17422 + 23796 + 2708 + 3923 + 1570 + 2690 + 102725 ns)
  spans   window 16975-99095673; sim.step 23811-5335965 and
          46958774-52267740; sim.overflow_check 5349168-46912521 and
          52281764-99079004
"""

import sys
from pathlib import Path

import pytest

from bench import harness, phases, trace
from bench.harness import Bench
from repro.core import telemetry

DATA = Path(__file__).resolve().parent / "testdata"
XPLANE = str(DATA / "scoped_step.xplane.pb")
GRID_NS = 41_813 + 20_685_544 + 43_432 + 25_798_233
SWEEP_NS = 157_636 + 161_928
UNSCOPED_NS = (192_110 + 23_556 + 26_963 + 2_022 + 3_445 + 1_437 + 2_291
               + 94_452 + 188_617 + 17_422 + 23_796 + 2_708 + 3_923 + 1_570
               + 2_690 + 102_725)
NEW_METRICS = ("sweep_busy_share", "grid_build_busy_share",
               "sweep_candidate_share", "setup_compile_s")


@pytest.fixture(scope="module")
def hlo():
    return (DATA / "scoped_step.hlo.txt").read_text()


@pytest.fixture(scope="module")
def by_phase(hlo):
    return phases.by_phase(trace.reduce(XPLANE, hlo))


@pytest.mark.parametrize("phase, ns", [
    ("grid_build", GRID_NS),
    ("neighbor_sweep", SWEEP_NS),
    (phases.UNSCOPED, UNSCOPED_NS),
])
def test_time_per_phase(by_phase, phase, ns):
    assert by_phase[phase] == pytest.approx(ns * 1e-9, abs=1e-12)


def test_phases_add_up_to_busy_time(by_phase, hlo):
    assert set(by_phase) == {"grid_build", "neighbor_sweep", phases.UNSCOPED}
    total = GRID_NS + SWEEP_NS + UNSCOPED_NS
    assert sum(by_phase.values()) == pytest.approx(total * 1e-9, abs=1e-12)
    assert trace.reduce(XPLANE, hlo).busy_s == pytest.approx(total * 1e-9,
                                                            abs=1e-12)


def test_gaps_named_by_the_program_spans(hlo):
    gaps = sorted(trace.reduce(XPLANE, hlo).gaps, key=lambda g: -g[1])
    # last op of call 1 to the first of call 2; its middle falls in the
    # first sim.overflow_check
    assert gaps[0] == ("sim.overflow_check",
                       pytest.approx((52_319_135 - 26_688_915) * 1e-9,
                                     abs=1e-12))
    assert gaps[1] == ("sim.overflow_check",
                       pytest.approx((99_095_673 - 78_682_286) * 1e-9,
                                     abs=1e-12))
    assert gaps[2] == ("sim.step",
                       pytest.approx((5_398_050 - 16_975) * 1e-9, abs=1e-12))
    assert not {"outside_spans", "run_call"} & {n for n, _ in gaps}
    names = [n for n, _ in trace.reduce(XPLANE, hlo).breakdown()["idle_gaps"]]
    assert names[:3] == ["sim.overflow_check", "sim.overflow_check",
                         "sim.step"]


def test_program_spans_are_loaded():
    _, spans = trace.load(XPLANE)      # the program's beside the harness's
    assert sorted(s.name for s in spans) == sorted(
        ["run_call", "run_call", "window"]
        + ["sim.step", "sim.overflow_check"] * 2)


def test_program_spans_leave_the_window_and_phases_unchanged(hlo):
    """The ``window`` span bounds the reduction: the program's spans only
    name gaps. The numbers are those of the harness's spans alone, as the
    reduction read them before it kept the program's."""
    ops, spans = trace.load(XPLANE)
    kinds = trace.hlo_kinds(hlo)
    harness_only = trace.reduce_events(
        ops, [s for s in spans if s.name in trace.SPANS], kinds)
    red = trace.reduce(XPLANE, hlo)
    harness_only.labels = red.labels
    assert red.by_name == harness_only.by_name
    assert sorted(s for _, s in red.gaps) == sorted(
        s for _, s in harness_only.gaps)
    idle = Bench().module("metrics", "device_idle_share")
    window_ns, total = 99_095_673 - 16_975, GRID_NS + SWEEP_NS + UNSCOPED_NS
    for r in (red, harness_only):
        assert r.window_s == pytest.approx(window_ns * 1e-9, abs=1e-12)
        assert r.busy_s == pytest.approx(total * 1e-9, abs=1e-12)
        assert idle.read(_context(r)) == pytest.approx(
            100.0 * (1 - total / window_ns), rel=1e-9)
        for phase, ns in (("grid_build", GRID_NS),
                          ("neighbor_sweep", SWEEP_NS),
                          (phases.UNSCOPED, UNSCOPED_NS)):
            assert phases.busy_share(r, phase) == pytest.approx(
                100.0 * ns / total, rel=1e-9)


@pytest.mark.parametrize("label, phase", [
    ("grid_build/jit(sort)/sort", "grid_build"),
    ("behaviors/infection/neighbor_sweep/while/body/gather",
     "neighbor_sweep"),
    ("grid_build/pairlist_build/while/body/cumsum", "pairlist_build"),
    ("vmap(jit(searchsorted))/grid_build/vmap()/while/body/gather",
     "grid_build"),
    ("neighbor_sweeps/gather", phases.UNSCOPED),
    ("", phases.UNSCOPED),
])
def test_innermost_phase_of_an_op_name(label, phase):
    assert phases.phase_of(label) == phase


def test_phase_names_are_the_engines():
    from repro.core import engine
    assert phases.PHASES == engine.PHASES


def test_compiled_sir_step_carries_the_phases():
    from bench import deploy
    bench = Bench()
    dep = deploy.deployment(bench.config("epidemiology"), agents=2048)
    sim = dep.simulation()
    s0 = deploy.initial_state(dep, sim, 2**33 + 5)
    labels = trace.hlo_labels(sim._step_fn.lower(s0).compile().as_text())
    found = {phases.phase_of(v) for v in labels.values()}
    assert {"grid_build", "neighbor_sweep", "behaviors"} <= found


def _context(reduction=None):
    red = reduction or trace.Reduction(window_s=1.0, busy_s=1.0, by_name={},
                                       by_kind={}, gaps=[])
    return harness.Context(params={}, reduction=red, device_kind="cpu",
                           seed=1)


@pytest.mark.parametrize("program", ["no_telemetry", "no_step_checked"])
@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_find_nothing_without_scopes_or_counters(
        name, program, monkeypatch):
    """A program without the scopes and counters, such as the one before
    them, or one whose run loop has checked no step, gives no reading."""
    import repro.core
    monkeypatch.setattr(telemetry, "_last_step", telemetry.StepRecord(
        {"sweep_slots": 4320, "sweep_candidates": 58}, 6.5))
    if program == "no_telemetry":          # its import fails
        monkeypatch.delattr(repro.core, "telemetry")
        monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    else:
        monkeypatch.setattr(telemetry, "_last_step", None)
    assert Bench().module("metrics", name).read(_context()) is None


@pytest.mark.parametrize("name, value", [
    ("sweep_busy_share", 60.0),
    ("grid_build_busy_share", 30.0),
    ("sweep_candidate_share", 100.0 * 58 / 4320),
    ("setup_compile_s", 6.5),
])
def test_new_readers_read(name, value, monkeypatch):
    red = trace.Reduction(
        window_s=2.5, busy_s=2.0, by_name={"a": 1.2, "b": 0.6, "c": 0.16,
                                           "d": 0.04},
        by_kind={}, gaps=[],
        labels={"a": "[gather] while/body/neighbor_sweep/gather",
                "b": "[gather] vmap(jit(searchsorted))/grid_build/gather",
                "c": "[other] behaviors/infection/add",
                "d": "[other]"})
    monkeypatch.setattr(telemetry, "_last_step", telemetry.StepRecord(
        {"sweep_slots": 4320, "sweep_candidates": 58}, 6.5))
    assert Bench().module("metrics", name).read(_context(red)) == \
        pytest.approx(value)


def test_traced_sir_run_reports_the_new_metrics():
    from bench.conftest import CPU
    import time
    result = harness.run_cell(Bench(), "epidemiology-sir", 2**35 + 3, 0.0,
                              True, time.perf_counter(), dict(CPU),
                              agents=2048)
    assert result["correct"], result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics)
    assert 0 < metrics["sweep_candidate_share"] < 100
    assert 0 < metrics["setup_compile_s"] <= telemetry.compile_seconds()
    shares = [metrics[m] for m in NEW_METRICS if m.endswith("busy_share")]
    assert all(s > 0 for s in shares) and sum(shares) <= 100 + 1e-9
