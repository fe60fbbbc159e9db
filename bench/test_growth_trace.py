"""The nested scopes of K1 and of the commit, and the per-layer metrics of
the oncology-growth cell that read them (``bench/subphases.py``,
``bench/work/k1.py``, ``bench/metrics/``).
"""

import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import deploy, phases, subphases, trace
from bench.conftest import CPU
from bench.harness import Bench, Context, run_cell
from bench.work import k1 as k1_work
from repro.core import telemetry

NEW_METRICS = ("k1_busy_share", "colmap_busy_share", "commit_busy_share",
               "k1_roofline_share", "colmap_fill_share")
V5E = "TPU v5 lite"


@pytest.mark.parametrize("label, scope", [
    ("neighbor_sweep/k1/while/body/k1_collision_force", "k1"),
    ("neighbor_sweep/k1/jit(_pad)/pad", "k1"),
    ("neighbor_sweep/colmap_build/while/body/sort", "colmap_build"),
    ("commit/deaths/reduce_sum", "deaths"),
    ("commit/compaction/cond/branch_1_fun/gather", "compaction"),
    ("commit/births/scatter", "births"),
    ("commit/reduce_sum", "commit"),
    ("neighbor_sweep/window_path/while/body/gather", "neighbor_sweep"),
    ("grid_build/sort", "grid_build"),
    ("k1s/add", phases.UNSCOPED),
])
def test_innermost_scope_of_an_op_name(label, scope):
    assert subphases.phase_of(label) == scope


@pytest.mark.parametrize("label", [
    "neighbor_sweep/k1/while/body/k1_collision_force",
    "neighbor_sweep/colmap_build/while/body/sort"])
def test_the_phases_file_nested_scopes_under_their_phase(label):
    """``bench/phases.py`` keeps reading the phases alone: K1's scopes are
    the sweep's there, as they were before they had scopes of their own."""
    assert phases.phase_of(label) == "neighbor_sweep"


def test_scope_names_are_the_engines():
    from repro.core import engine
    assert subphases.SUBPHASES == engine.SUBPHASES
    assert subphases.NAMES == engine.PHASES + engine.SUBPHASES


def test_compiled_oncology_step_carries_the_scopes():
    bench = Bench()
    dep = deploy.deployment(bench.config("oncology"), agents=2048)
    sim = dep.simulation()
    s0 = deploy.initial_state(dep, sim, 2**33 + 5)
    labels = trace.hlo_labels(sim._step_fn.lower(s0).compile().as_text())
    found = {subphases.phase_of(v) for v in labels.values()}
    assert set(subphases.SUBPHASES) <= found


def _context(reduction=None, engine=None, kind="cpu"):
    red = reduction or trace.Reduction(window_s=1.0, busy_s=1.0, by_name={},
                                       by_kind={}, gaps=[])
    return Context(params={"engine": engine or {"colmap_width": 64}},
                   reduction=red, device_kind=kind, seed=1)


COUNTS = {"colmap_demand": 24, "k1_tiles": 30_000, "k1_live_rows": 40_000}
RED = trace.Reduction(
    window_s=2.5, busy_s=2.0,
    by_name={"a": 0.7, "b": 0.4, "c": 0.2, "d": 0.1, "e": 0.05, "f": 0.45,
             "g": 0.1},
    by_kind={}, gaps=[],
    labels={"a": "[other] neighbor_sweep/k1/while/body/k1_collision_force/"
                 "pallas_call",
            "g": "[other] neighbor_sweep/k1/concatenate",
            "b": "[sort] neighbor_sweep/colmap_build/while/body/sort",
            "c": "[gather] commit/compaction/cond/gather",
            "d": "[scatter] commit/births/scatter",
            "e": "[other] commit/deaths/and",
            "f": "[gather] grid_build/gather"})


@pytest.mark.parametrize("name, value, kind", [
    ("k1_busy_share", 40.0, "cpu"),
    ("colmap_busy_share", 20.0, "cpu"),
    ("commit_busy_share", 17.5, "cpu"),
    ("colmap_fill_share", 37.5, "cpu"),
    ("k1_roofline_share",
     100.0 * k1_work.least_seconds(2 * 30_000, 2 * 40_000, V5E) / 0.7, V5E),
])
def test_new_readers_read(name, value, kind, monkeypatch):
    monkeypatch.setattr(telemetry, "_last_step",
                        telemetry.StepRecord(COUNTS, 1.0))
    monkeypatch.setattr(telemetry, "_totals", telemetry.StepTotals(
        2, {k: 2 * v for k, v in COUNTS.items()}))
    got = Bench().module("metrics", name).read(_context(RED, kind=kind))
    assert got == pytest.approx(value)


COUNTERS = ("k1_roofline_share", "colmap_fill_share")


@pytest.mark.parametrize("name, program", [
    (name, "parent") for name in NEW_METRICS] + [
    (name, program) for name in COUNTERS
    for program in ("no_telemetry", "no_step_checked")])
def test_new_readers_find_nothing_in_a_program_without_them(
        name, program, monkeypatch):
    """The parent's program, whose trace has none of the nested scopes and
    whose run loop keeps no K1 counters, gives no reading; nor does a
    program without telemetry, or a run that checked no step."""
    import repro.core
    monkeypatch.setattr(telemetry, "_last_step",
                        telemetry.StepRecord(COUNTS, 1.0))
    monkeypatch.setattr(telemetry, "_totals",
                        telemetry.StepTotals(1, dict(COUNTS)))
    red = RED
    if program == "no_telemetry":
        monkeypatch.delattr(repro.core, "telemetry")
        monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    elif program == "no_step_checked":
        monkeypatch.setattr(telemetry, "_last_step", None)
        monkeypatch.setattr(telemetry, "_totals", None)
    else:
        parent = {"sweep_slots": 10.0, "sweep_candidates": 1}
        red = trace.Reduction(
            window_s=2.5, busy_s=2.0, by_name={"a": 1.2, "f": 0.8},
            by_kind={}, gaps=[],
            labels={"a": "[other] neighbor_sweep/while/body/k1_collision",
                    "f": "[gather] grid_build/gather"})
        monkeypatch.setattr(telemetry, "_last_step",
                            telemetry.StepRecord(parent, 1.0))
        monkeypatch.setattr(telemetry, "_totals",
                            telemetry.StepTotals(1, parent))
    assert Bench().module("metrics", name).read(
        _context(red, kind=V5E)) is None


def test_k1_counts_stay_within_the_kernels_own_work():
    """What the roofline counts per tile is at most what K1 does: its
    elementwise ops on a 128×128 tile, and one (8, 128) float32 column
    operand read a tile; so a share over 100% means the time is short."""
    from repro.kernels import collision_force as k1
    row = jnp.zeros((8, k1.BLOCK), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda r, c: k1._force_tile(
        r, c, jnp.int32(0), jnp.int32(0), 2.0, None, 0.4))(row, row)
    tile_ops = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name not in ("broadcast_in_dim", "iota",
                                            "convert_element_type")
                and any(getattr(v.aval, "shape", None)
                        == (k1.BLOCK, k1.BLOCK) for v in e.outvars)]
    assert k1_work.OPS_PER_PAIR <= len(tile_ops)
    assert k1_work.BLOCK == k1.BLOCK
    assert k1_work.TILE_BYTES == row.size * row.dtype.itemsize
    assert k1_work.bound(30_000, 40_000, V5E) == "compute"
    with pytest.raises(KeyError):
        k1_work.least_seconds(1, 1, "cpu")


def test_traced_oncology_run_reports_the_new_metrics(monkeypatch):
    """At the CPU size every new metric is read but the roofline share,
    which has no peak for the CPU; the run's K1 counters sum its two traced
    steps."""
    monkeypatch.setattr(telemetry, "_totals", None)
    result = run_cell(Bench(), "oncology-growth", 2**35 + 3, 0.0, True,
                      time.perf_counter(), dict(CPU), agents=2048)
    assert result["correct"], result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) - {"k1_roofline_share"} <= set(metrics)
    assert "k1_roofline_share" not in metrics
    shares = [metrics[m] for m in NEW_METRICS if m.endswith("busy_share")]
    assert all(0 < s < 100 for s in shares) and sum(shares) <= 100 + 1e-9
    assert 0 < metrics["colmap_fill_share"] <= 100
    totals = telemetry.step_totals()
    assert totals.steps == 2 and totals.counts["k1_tiles"] > 0
