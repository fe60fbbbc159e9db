"""Shared helpers of the benchmark's CPU tests: cells run at tiny sizes."""

from bench import deploy, harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def step_pair(cell_name: str, agents: int, population=None, steps: int = 2,
              seed: int = 2**35 + 11):
    """Run a cell's deployment ``steps`` steps through ``Simulation.run``;
    the live arrays entering and leaving the last step, with its context."""
    bench = harness.Bench()
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    pop = dict(cell.get("population", {}), **(population or {}))
    dep = deploy.deployment(config, pop, agents)
    sim = dep.simulation()
    state = deploy.initial_state(dep, sim, seed)
    for _ in range(steps):
        prev, state = state, sim.run(state, 1, check_overflow=True)
    before, after = harness.live_arrays(prev), harness.live_arrays(state)
    after["births"] = int(state.stats.births)
    return bench, cell, config, dep, before, after

