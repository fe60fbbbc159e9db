"""Epidemiology (paper Table 1): spatial SIR with random agent movement.

Prints the classic SIR curves. Demonstrates: neighbor-radius infection via
the uniform grid, no mechanical forces, random walk movement.

    PYTHONPATH=src python examples/epidemiology.py

Running distributed
-------------------
The same scenario runs sharded over devices without touching the model:
every slab executes the shared iteration core (DESIGN.md §7), so behaviors,
births/deaths and the infection state cross slab boundaries automatically.
On a CPU-only machine, fake 4 devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/epidemiology.py --distributed
"""

import os
import sys

import numpy as np

from repro.core import DistConfig, DistributedSimulation, EngineConfig, Simulation
from repro.core.behaviors import (Infection, RandomWalk, INFECTED,
                                  RECOVERED, SUSCEPTIBLE)

N_AGENTS = int(os.environ.get("EXAMPLE_N", 20_000))   # CI smoke caps size
EPOCHS = int(os.environ.get("EXAMPLE_EPOCHS", 10))
SIDE = 140.0


def make_config() -> EngineConfig:
    return EngineConfig(capacity=N_AGENTS, domain_lo=(0, 0, 0),
                        domain_hi=(SIDE,) * 3, interaction_radius=3.0,
                        use_forces=False, query_chunk=4096, max_per_box=32)


def behaviors():
    return [RandomWalk(sigma=0.8),
            Infection(radius=3.0, beta=0.25, recovery_time=40)]


def initial_population(rng):
    pos = rng.uniform(0, SIDE, (N_AGENTS, 3)).astype(np.float32)
    types = np.zeros(N_AGENTS, np.int32)
    types[:20] = INFECTED
    return pos, types


def report(iteration, agent_type, alive):
    t = np.asarray(agent_type)[np.asarray(alive)]
    print(f"{int(iteration):5d} {(t == SUSCEPTIBLE).sum():7d} "
          f"{(t == INFECTED).sum():7d} {(t == RECOVERED).sum():7d}")
    return t


def main():
    rng = np.random.default_rng(1)
    pos, types = initial_population(rng)
    sim = Simulation(make_config(), behaviors())
    state = sim.init_state(pos, diameter=np.full(N_AGENTS, 1.0, np.float32),
                           agent_type=types,
                           extra_init={"infect_timer": np.full(N_AGENTS, 40,
                                                               np.int32)})
    print(f"{'iter':>5} {'S':>7} {'I':>7} {'R':>7}")
    for epoch in range(EPOCHS):
        state = sim.run(state, 20, check_overflow=True)
        t = report(state.iteration, state.pool.agent_type, state.pool.alive)
    assert (t != SUSCEPTIBLE).sum() > 20, "epidemic should have spread"
    print("OK: epidemic spread and recovered")


def main_distributed(n_shards: int = 4):
    """The "running distributed" path: same config + behaviors, quantile
    x-slabs with in-loop rebalance; RandomWalk draws differ per shard, so
    curves are statistically (not bitwise) equal to the single-device run."""
    import jax
    devices = jax.devices()
    if len(devices) < n_shards:
        raise SystemExit(f"need {n_shards} devices; found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    rng = np.random.default_rng(1)
    pos, types = initial_population(rng)
    local_capacity = 2 * N_AGENTS // n_shards
    dcfg = DistConfig(engine=make_config(), n_shards=n_shards,
                      local_capacity=local_capacity,
                      halo_capacity=min(4096, local_capacity),
                      migrate_capacity=min(2048, local_capacity),
                      rebalance_frequency=10)
    dsim = DistributedSimulation(dcfg, behaviors())
    state = dsim.init_state(pos, diameter=np.full(N_AGENTS, 1.0, np.float32),
                            agent_type=types,
                            extra_init={"infect_timer": np.full(N_AGENTS, 40,
                                                                np.int32)})
    print(f"{'iter':>5} {'S':>7} {'I':>7} {'R':>7}   (over {n_shards} shards)")
    for epoch in range(EPOCHS):
        state = dsim.run(state, 20, check_overflow=True)
        t = report(state.iteration, state.channels["agent_type"],
                   state.channels["alive"])
        print(f"      per-shard live: "
              f"{np.asarray(state.stats['n_live']).tolist()}")
    assert (t != SUSCEPTIBLE).sum() > 20, "epidemic should have spread"
    print("OK: epidemic spread and recovered (distributed)")


if __name__ == "__main__":
    if "--distributed" in sys.argv:
        main_distributed()
    else:
        main()
