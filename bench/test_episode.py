"""Each cell replays a fixed episode from its set-up state, so the work of
each step of the window does not depend on how many steps fit in it."""

import jax
import numpy as np

from bench import deploy, harness


def test_window_replays_the_episode():
    bench = harness.Bench()
    cell = bench.cell("epidemiology-sir")
    dep = deploy.deployment(bench.config(cell["config"]),
                            {"seed_type": {"value": 1, "share": 0.05,
                                           "min": 5}}, agents=2048)
    sim = dep.simulation()
    s0 = jax.block_until_ready(deploy.initial_state(dep, sim, 2**36 + 1))
    episode = 4

    def window(steps):
        return harness.solo_window(sim, s0, dep.n_agents, episode,
                                   steps=steps)

    def position(state):
        return harness.live_arrays(state)["position"]

    short, long = window(2), window(3 * episode + 2)
    assert short.failed == long.failed == 0
    assert long.entering == [dep.n_agents] * (3 * episode + 2)
    assert long.agent_steps == sum(long.entering)
    assert window(episode + 1).last[0] is s0          # the episode restarts
    assert not np.array_equal(position(window(episode).last[1]),
                              position(s0))             # the walk moves
    for a, b in zip(short.last, long.last):          # and repeats exactly
        for key, value in harness.live_arrays(a).items():
            assert np.array_equal(value, harness.live_arrays(b)[key]), key
