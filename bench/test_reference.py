"""The plain references against the engine's step, and their controls.

At a CPU test size, the numbers comparing a step of the program with the
reference lie within the cell's limits, and the control (the reference put
in the program's place, computed one precision lower) exceeds at least one.
The epidemiology case seeds 5% infected, so that enough agents are exposed
for the bfloat16 control to show at this small a domain.
"""

import numpy as np
import pytest

from bench import harness
from bench.conftest import step_pair

CASES = [("epidemiology-sir", 16384,
          {"seed_type": {"value": 1, "share": 0.05, "min": 5}})]


@pytest.fixture(scope="module", params=CASES, ids=[c[0] for c in CASES])
def stepped(request):
    cell_name, agents, population = request.param
    return step_pair(cell_name, agents, population)


def _numbers(stepped, control: bool):
    bench, cell, config, dep, before, after = stepped
    ref = bench.module("reference", config["reference"])
    chk = cell["check"]
    params = harness.reference_params(config, dep)
    sample = harness.sample_of(len(before["diameter"]), chk.get("sample", 0),
                               7)
    if control:
        return ref.control_numbers(before, params, sample, chk, 7), chk
    return ref.numbers(before, after, params, sample, chk), chk


def test_reference_agrees_with_the_step(stepped):
    numbers, chk = _numbers(stepped, control=False)
    assert set(numbers) == set(chk["limits"])
    for name, value in numbers.items():
        assert value <= chk["limits"][name], (name, value)


def test_lower_precision_control_fails(stepped):
    numbers, chk = _numbers(stepped, control=True)
    over = [n for n, v in numbers.items() if v > chk["limits"][n]]
    assert over, numbers


def test_the_sample_follows_the_seed():
    a = harness.sample_of(100_000, 4096, 2**40 + 1)
    assert len(a) == len(np.unique(a)) == 4096
    assert np.array_equal(a, harness.sample_of(100_000, 4096, 2**40 + 1))
    assert not np.array_equal(a, harness.sample_of(100_000, 4096, 5))
    assert np.array_equal(harness.sample_of(10, 4096, 3), np.arange(10))
