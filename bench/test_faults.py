"""A run whose timed path is broken underneath comes out not correct.

Each case drives the harness's whole run on the CPU (``run_cell``, past the
look for a chip) with the program broken in one of the ways a cell of this
benchmark can be (``bench/faults.py``). The epidemiology cell runs with 5%
infected, so that enough agents are exposed at this size for a sweep that
finds nothing to show against the binomial spread.
"""

import time

import pytest

from bench import faults, harness
from bench.conftest import CPU

SIZES = {"epidemiology-sir": (16384,
                             {"seed_type": {"value": 1, "share": 0.05,
                                            "min": 5}})}


def run(cell_name):
    agents, population = SIZES[cell_name]
    return harness.run_cell(harness.Bench(), cell_name, 2**33 + 5, 0.5,
                            False, time.perf_counter(), dict(CPU),
                            agents=agents, population=population)


@pytest.mark.parametrize("cell_name", sorted(SIZES))
def test_sound_run_is_correct(cell_name):
    result = run(cell_name)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell_name,fault", [
    (cell, fault) for cell in sorted(faults.CELLS)
    for fault in faults.CELLS[cell]])
def test_broken_step_is_not_correct(cell_name, fault):
    with faults.planted(fault):
        result = run(cell_name)
    assert not result["correct"], result["checks"]
