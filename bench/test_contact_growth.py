"""The growing spheroid's plain reference against the engine's step, its
control and its faults (``bench/reference/contact_growth.py``,
``bench/faults_growth.py``).

At a CPU size the oncology deployment steps through ``Simulation.run`` with
K1 in interpret mode: over several steps the numbers comparing each step
with the reference lie within the cell's limits, the control (the reference
put in the program's place, computed in bfloat16) exceeds at least one, and
a whole run through the harness is correct, and not correct with any of the
cell's faults planted.
"""

import time

import numpy as np
import pytest

from bench import deploy, faults_growth, harness
from bench.conftest import CPU

CELL = "oncology-growth"
AGENTS = 4096          # the reference's cases; the faults' runs take twice
                       # as many, so that skipped deaths show against the
                       # binomial spread


def _episode(seed, steps=4):
    """(before, after) live arrays of each of ``steps`` steps of the cell's
    deployment at AGENTS seeds, with its context."""
    bench = harness.Bench()
    cell = bench.cell(CELL)
    config = bench.config(cell["config"])
    dep = deploy.deployment(config, cell.get("population"), AGENTS)
    sim = dep.simulation()
    state = deploy.initial_state(dep, sim, seed)
    pairs = []
    for _ in range(steps):
        prev, state = state, sim.run(state, 1, check_overflow=True)
        pairs.append(harness.host_pair(prev, state))
    return bench, cell, config, dep, pairs


@pytest.fixture(scope="module", params=[2**35 + 11, 3_000_000_019],
                ids=["seed_a", "seed_b"])
def episode(request):
    return _episode(request.param)


def _numbers(episode, control: bool):
    bench, cell, config, dep, pairs = episode
    ref = bench.module("reference", config["reference"])
    chk = cell["check"]
    params = harness.reference_params(config, dep)
    out = []
    for before, after in pairs:
        sample = harness.sample_of(len(before["diameter"]), chk["sample"], 7)
        out.append(ref.control_numbers(before, params, sample, chk, 7)
                   if control else
                   ref.numbers(before, after, params, sample, chk))
    return out, chk


def test_reference_agrees_with_every_step(episode):
    steps, chk = _numbers(episode, control=False)
    for numbers in steps:
        assert set(numbers) == set(chk["limits"])
        for name, value in numbers.items():
            assert value <= chk["limits"][name], (name, value)
        assert numbers["bad_share"] == 0.0


def test_the_episode_grows_divides_and_dies(episode):
    *_, pairs = episode
    for before, after in pairs:
        n_in, n_out = len(before["diameter"]), len(after["diameter"])
        assert after["births"] > 0 and n_out - after["births"] < n_in
    first, last = pairs[0][0], pairs[-1][1]
    assert len(last["diameter"]) > len(first["diameter"])
    # every cell descends from a seed, and daughters share its label
    assert set(np.unique(last["extra.clone"])) <= set(first["extra.clone"])
    assert len(np.unique(last["extra.clone"])) < len(last["extra.clone"])


def test_lower_precision_control_fails(episode):
    steps, chk = _numbers(episode, control=True)
    for numbers in steps:
        over = [n for n, v in numbers.items() if v > chk["limits"][n]]
        assert over, numbers
        assert "force_z" in over and "death_z" not in over, numbers


def _run(agents=2 * AGENTS, trace=False):
    return harness.run_cell(harness.Bench(), CELL, 2**33 + 5, 0.5, trace,
                            time.perf_counter(), dict(CPU), agents=agents)


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("fault", faults_growth.CELLS[CELL])
def test_broken_step_is_not_correct(fault):
    with faults_growth.planted(fault):
        result = _run()
    assert not result["correct"], result["checks"]
