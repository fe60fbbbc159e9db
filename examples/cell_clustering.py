"""Cell clustering (paper Table 1): chemotaxis toward self-secreted substance.

Agents secrete a diffusing chemoattractant and climb its gradient — the
engine's diffusion substrate + behavior composition. Mean pairwise distance
shrinks as clusters form.

    PYTHONPATH=src python examples/cell_clustering.py

``--pairlist`` adds contact mechanics (cells resist overlap as clusters
densify) served from the Verlet pair-list cache (DESIGN.md §3.4): the grid
rebuild is amortized every-k steps and the force sweep runs over the pruned
in-range(+skin) pair table, reused while no agent moves farther than
``--skin``/2. Each epoch prints the realized listed pairs per agent.
"""

import argparse
import os

import numpy as np

from repro.core import (EngineConfig, ForceParams, PairListConfig,
                        RebuildPolicy, Simulation)
from repro.core.behaviors import Chemotaxis, Secretion
from repro.core.diffusion import DiffusionSpec


def mean_pairwise(p, k=512):
    idx = np.random.default_rng(0).choice(len(p), size=min(k, len(p)), replace=False)
    q = p[idx]
    d = np.sqrt(((q[:, None] - q[None]) ** 2).sum(-1))
    return d[np.triu_indices(len(q), 1)].mean()


N_AGENTS = int(os.environ.get("EXAMPLE_N", 4_000))     # CI smoke caps size
SIDE = 64.0


def make_config(pairlist: bool = False, skin: float = 1.5) -> EngineConfig:
    extra = dict(use_forces=False)
    if pairlist:
        extra = dict(
            use_forces=True,
            # cap the per-step contact resolution so motion stays inside the
            # skin budget (reuse requires max step distance <= skin/2)
            force=ForceParams(max_displacement=0.25),
            rebuild=RebuildPolicy(mode="every_k", k=8,
                                  displacement_bound=skin / 2),
            pairlist=PairListConfig(skin=skin, max_pairs=64))
    return EngineConfig(
        capacity=N_AGENTS, domain_lo=(0, 0, 0), domain_hi=(SIDE,) * 3,
        interaction_radius=3.0, query_chunk=4096,
        diffusion=DiffusionSpec(dims=(32, 32, 32), coefficient=0.5,
                                decay=0.01, voxel=2.0), **extra)


def behaviors():
    return [Secretion(rate=2.0), Chemotaxis(speed=0.35)]


def pairs_per_agent(state) -> float:
    """Mean listed in-range(+skin) candidates per live agent — resident
    rows of the cached pair table, averaged over the live mask."""
    alive = np.asarray(state.pool.alive)
    count = np.asarray(state.env.pairs.count)
    n_live = max(int(alive.sum()), 1)
    return float(count[alive].sum()) / n_live


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pairlist", action="store_true",
                    help="contact forces via the Verlet pair-list cache")
    ap.add_argument("--skin", type=float, default=1.5,
                    help="pair-list skin (reuse while motion <= skin/2)")
    args = ap.parse_args()
    rng = np.random.default_rng(4)
    n = N_AGENTS
    epochs = int(os.environ.get("EXAMPLE_EPOCHS", 6))
    side = SIDE
    sim = Simulation(make_config(args.pairlist, args.skin), behaviors())
    pos = rng.uniform(4, side - 4, (n, 3)).astype(np.float32)
    dia = 2.0 if args.pairlist else 1.0
    state = sim.init_state(pos, diameter=np.full(n, dia, np.float32))
    p0 = np.asarray(state.pool.position[:n])
    print(f"initial mean pairwise distance: {mean_pairwise(p0):.2f}")
    for epoch in range(epochs):
        if args.pairlist:
            skips = 0
            for _ in range(10):
                state = sim.run(state, 1, check_overflow=True)
                skips += 1 - int(state.stats.rebuilds)
            pl = (f"  pairs/agent {pairs_per_agent(state):.1f}"
                  f"  reused {skips}/10 steps")
        else:
            state = sim.run(state, 10, check_overflow=True)
            pl = ""
        p = np.asarray(state.pool.position[:n])
        print(f"iter {int(state.iteration):3d}: mean pairwise "
              f"{mean_pairwise(p):.2f}  substance max "
              f"{float(state.conc.max()):.1f}{pl}")
    assert mean_pairwise(np.asarray(state.pool.position[:n])) < mean_pairwise(p0)
    print("OK: clusters formed")


if __name__ == "__main__":
    main()
