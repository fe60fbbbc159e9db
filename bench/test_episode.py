"""Each cell replays a fixed episode from its set-up state, so the work of
each step of the window does not depend on how many steps fit in it; nor
do the device states the window holds, nor the steps it checks."""

import gc
import time

import jax
import numpy as np
import pytest

from bench import deploy, harness
from bench.conftest import CPU
from repro.core import engine

EPISODE = 4


@pytest.fixture(scope="module")
def sir():
    """The SIR cell's simulation at 2,048 agents, its initial state and
    population."""
    bench = harness.Bench()
    cell = bench.cell("epidemiology-sir")
    dep = deploy.deployment(bench.config(cell["config"]),
                            {"seed_type": {"value": 1, "share": 0.05,
                                           "min": 5}}, agents=2048)
    sim = dep.simulation()
    s0 = jax.block_until_ready(deploy.initial_state(dep, sim, 2**36 + 1))
    return sim, s0, dep.n_agents


class Recording:
    """A simulation that records the device bytes alive at each dispatch."""

    def __init__(self, sim):
        self.sim, self.live_bytes = sim, []

    def run(self, state, n, **kw):
        self.live_bytes.append(sum(x.nbytes for x in jax.live_arrays()))
        return self.sim.run(state, n, **kw)


def _raise_from(monkeypatch, k):
    """``Simulation.run`` patched so that from its ``k``-th call on it runs
    the step and then raises, as the run loop does on an overflow flag."""
    run, calls = engine.Simulation.run, []

    def raising(self, state, n, **kw):
        calls.append(n)
        out = run(self, state, n, **kw)
        if len(calls) >= k:
            raise RuntimeError("planted overflow")
        return out

    monkeypatch.setattr(engine.Simulation, "run", raising)


def _plain_pair(states, i):
    """Step ``i``'s (before, after) live arrays from a plain loop's states,
    with the step's birth count."""
    before = harness.live_arrays(states[i])
    after = harness.live_arrays(states[i + 1])
    after["births"] = int(states[i + 1].stats.births)
    return before, after


def _assert_pairs_equal(got, want):
    assert len(got) == len(want)
    for pair, want_pair in zip(got, want):
        for arrays, want_arrays in zip(pair, want_pair):
            assert arrays.keys() == want_arrays.keys()
            for key, value in want_arrays.items():
                assert np.array_equal(arrays[key], value), key


def test_window_replays_the_episode(sir):
    sim, s0, n0 = sir

    def window(steps):
        return harness.solo_window(sim, s0, n0, EPISODE, steps=steps)

    def position(state):
        return harness.live_arrays(state)["position"]

    short, long = window(2), window(3 * EPISODE + 2)
    assert short.failed == long.failed == 0
    assert long.entering == [n0] * (3 * EPISODE + 2)
    assert long.agent_steps == sum(long.entering)
    assert window(EPISODE + 1).last[0] is s0          # the episode restarts
    assert not np.array_equal(position(window(EPISODE).last[1]),
                              position(s0))             # the walk moves
    for a, b in zip(short.last, long.last):          # and repeats exactly
        for key, value in harness.live_arrays(a).items():
            assert np.array_equal(value, harness.live_arrays(b)[key]), key
    # a window of two steps keeps its first pair on the device; a longer one
    # has copied it to the host, with the same arrays
    assert short.first[0] is s0
    assert all(isinstance(arrays, dict) for arrays in long.first)
    _assert_pairs_equal([long.first], [harness.host_pair(*short.first)])


def test_window_device_bytes_do_not_grow_with_its_steps(sir):
    sim, s0, n0 = sir
    jax.block_until_ready(sim.run(s0, 1, check_overflow=True))   # compiled

    def most_live_bytes(steps):
        gc.collect()
        rec = Recording(sim)
        harness.solo_window(rec, s0, n0, EPISODE, steps=steps)
        assert len(rec.live_bytes) == steps
        return max(rec.live_bytes)

    assert most_live_bytes(2) == most_live_bytes(3 * EPISODE + 2)


def test_checked_steps_are_the_first_and_last_of_a_plain_loop(sir):
    sim, s0, n0 = sir
    states = [s0]
    for _ in range(3):
        states.append(sim.run(states[-1], 1, check_overflow=True))
    win = harness.solo_window(sim, s0, n0, EPISODE, steps=3)
    _assert_pairs_equal(harness.checked_steps(win),
                        [_plain_pair(states, 0), _plain_pair(states, 2)])
    one = harness.solo_window(sim, s0, n0, EPISODE, steps=1)
    _assert_pairs_equal(harness.checked_steps(one), [_plain_pair(states, 0)])


def test_a_failed_last_dispatch_checks_the_first_pair_alone(sir,
                                                            monkeypatch):
    sim, s0, n0 = sir
    states = [s0, sim.run(s0, 1, check_overflow=True)]
    _raise_from(monkeypatch, 2)
    win = harness.solo_window(sim, s0, n0, EPISODE, steps=2)
    assert (win.attempted, win.failed, win.failed_at) == (2, 1, [2])
    _assert_pairs_equal(harness.checked_steps(win), [_plain_pair(states, 0)])


@pytest.mark.parametrize("raising_from", [1, 2])
def test_a_window_whose_step_raises_is_not_correct(raising_from, monkeypatch):
    """The traced run's two steps, the first or the second raising: the run
    still gives its result, not correct; it checks what completed."""
    _raise_from(monkeypatch, raising_from)
    result = harness.run_cell(harness.Bench(), "epidemiology-sir", 2**35 + 17,
                              0.0, True, time.perf_counter(), dict(CPU),
                              agents=2048)
    assert not result["correct"]
    assert result["attempted"] == 2
    assert result["failed"] == 3 - raising_from
    assert bool(result["checks"]) == (raising_from == 2), result["checks"]
