"""What the program records of itself (``repro.core.telemetry``): compile
seconds from JAX's compile events, merged where they nest, and the work
counters of the last step that ``Simulation.run`` checked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, Simulation, StepStats, telemetry
from repro.core.behaviors import INFECTED, Infection


@pytest.fixture
def fresh(monkeypatch):
    """Telemetry with nothing recorded, restored afterwards."""
    monkeypatch.setattr(telemetry, "_compiles", [])
    monkeypatch.setattr(telemetry, "_compile_s", 0.0)
    monkeypatch.setattr(telemetry, "_last_step", None)
    monkeypatch.setattr(telemetry, "_totals", None)
    return telemetry


def test_nested_compile_events_count_once(fresh, monkeypatch):
    clock = iter([10.0, 12.0, 13.0, 14.0])
    monkeypatch.setattr(telemetry.time, "perf_counter", lambda: next(clock))
    prefix = telemetry.COMPILE_EVENTS
    fresh._on_duration(prefix + "jaxpr_trace_duration", 1.0)     # [9, 10]
    fresh._on_duration(prefix + "jaxpr_trace_duration", 3.5)     # [8.5, 12]
    fresh._on_duration("/jax/compilation_cache/compile_time_saved_sec",
                       100.0)
    fresh._on_duration(prefix + "backend_compile_duration", 0.5)  # [12.5, 13]
    fresh._on_duration(prefix + "jaxpr_to_mlir_module_duration", 0.25)
    assert fresh.compile_seconds() == pytest.approx(3.5 + 0.5 + 0.25)


def test_a_compile_is_counted(fresh):
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0))
    assert fresh.compile_seconds() > 0


def test_run_records_the_last_checked_step(fresh):
    n, side = 512, 30.0
    rng = np.random.default_rng(3)
    sim = Simulation(
        EngineConfig(capacity=n, domain_lo=(0.0,) * 3,
                     domain_hi=(side,) * 3, interaction_radius=3.0,
                     use_forces=False, query_chunk=128),
        [Infection(radius=3.0, beta=0.5, recovery_time=8)])
    types = np.where(np.arange(n) < n // 10, INFECTED, 0).astype(np.int32)
    s0 = sim.init_state(rng.uniform(0.5, side - 0.5, (n, 3)), agent_type=types,
                        extra_init={"infect_timer": np.full(n, 8, np.int32)})
    sim.run(s0, 1)
    assert fresh.last_step() is None          # no flags checked, no record
    state = sim.run(s0, 2, check_overflow=True)
    step = fresh.last_step()
    assert set(step.counts) == set(StepStats.WORK_FIELDS)
    assert step.counts == {f: int(state.stats[f])
                           for f in StepStats.WORK_FIELDS}
    assert step.counts["sweep_slots"] > step.counts["sweep_candidates"] > 0
    assert 0 < step.compile_s <= fresh.compile_seconds()


def test_run_sums_the_checked_steps(fresh):
    """``step_totals`` adds up the work counters of every checked step."""
    n, side = 256, 24.0
    rng = np.random.default_rng(4)
    sim = Simulation(
        EngineConfig(capacity=n, domain_lo=(0.0,) * 3,
                     domain_hi=(side,) * 3, interaction_radius=3.0,
                     use_forces=False, query_chunk=128),
        [Infection(radius=3.0, beta=0.5, recovery_time=8)])
    types = np.where(np.arange(n) < n // 8, INFECTED, 0).astype(np.int32)
    s0 = sim.init_state(rng.uniform(0.5, side - 0.5, (n, 3)), agent_type=types,
                        extra_init={"infect_timer": np.full(n, 8, np.int32)})
    assert fresh.step_totals() is None
    seen = []
    sim.run(s0, 3, check_overflow=True,
            callback=lambda i, st: seen.append(
                {f: int(st.stats[f]) for f in StepStats.WORK_FIELDS}))
    totals = fresh.step_totals()
    assert totals.steps == 3
    assert totals.counts == {f: sum(c[f] for c in seen)
                             for f in StepStats.WORK_FIELDS}
    assert fresh.last_step().counts == seen[-1]
