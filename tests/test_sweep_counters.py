"""The fused sweep's work counters (StepStats ``sweep_*``) against a plain
numpy count.

One SIR step (Infection alone: no forces, no walk) of a small uniform
population, in the streamed and in the pair-list mode of
grid.resident_apply_fused. Capacity equals the population and the query
block divides it, so every block is visited and every row is a live agent:
``sweep_slots`` is the population times the slots per row, and
``sweep_candidates`` the number of (agent, other agent) pairs whose grid
boxes touch (the 3×3×3 boxes around the agent's own), or, from the pair
list, those of them within the list's radius, at most ``max_pairs`` an
agent. A step that runs no fused sweep leaves both at 0. Lanes of the
ensemble count their own populations, so summed over lanes the counters
and their ratio stay those of the whole sweep.
"""

import itertools

import numpy as np
import pytest

from repro.core import EngineConfig, EnsembleEngine, Simulation, grid
from repro.core.behaviors import INFECTED, Infection

N, SIDE, CHUNK, MAX_PAIRS = 1024, 40.0, 256, 48


def _cfg(mode):
    return EngineConfig(
        capacity=N, domain_lo=(0.0,) * 3, domain_hi=(SIDE,) * 3,
        interaction_radius=3.0, use_forces=False, max_per_box=32,
        query_chunk=CHUNK, fused_sweep=mode != "unfused",
        pairlist=(grid.PairListConfig(skin=0.0, max_pairs=MAX_PAIRS)
                  if mode == "pairlist" else None))


def _population(seed):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.5, SIDE - 0.5, (N, 3)).astype(np.float32)
    types = np.where(np.arange(N) < N // 10, INFECTED, 0).astype(np.int32)
    return (pos, np.ones(N, np.float32), types,
            {"infect_timer": np.full(N, 8, np.int32)})


def _behaviors():
    return [Infection(radius=3.0, beta=0.5, recovery_time=8)]


def _plain_count(pos, cfg, mode):
    """(slots, candidates) of one sweep over every agent, by numpy."""
    dims = np.asarray(cfg.grid_spec.dims)
    rel = (pos - np.float32(0.0)) / np.float32(cfg.cell_size)
    cell = np.clip(np.floor(rel).astype(np.int64), 0, dims - 1)
    near = (np.abs(cell[:, None, :] - cell[None, :, :]) <= 1).all(-1)
    np.fill_diagonal(near, False)
    if mode == "unfused":
        return 0, 0
    if mode == "streamed":
        return N * 9 * cfg.grid_spec.run_capacity, int(near.sum())
    d = pos[None, :, :] - pos[:, None, :]
    in_reach = near & (np.sum(d * d, axis=-1)
                       <= np.square(np.float32(cfg.interaction_radius)))
    return N * MAX_PAIRS, int(np.minimum(in_reach.sum(1), MAX_PAIRS).sum())


@pytest.mark.parametrize("mode", ["streamed", "pairlist", "unfused"])
def test_sweep_counters_equal_a_plain_count(mode):
    pos, dia, types, extra = _population(5)
    cfg = _cfg(mode)
    sim = Simulation(cfg, _behaviors())
    stats = sim.step(sim.init_state(pos, diameter=dia, agent_type=types,
                                    extra_init=extra)).stats
    assert not stats.flags()                   # no z-run or list truncated
    got = (int(stats.sweep_slots), int(stats.sweep_candidates))
    assert got == _plain_count(pos, cfg, mode)


def test_ensemble_lanes_count_their_own_sweeps():
    cfg, seeds = _cfg("streamed"), (5, 6)
    eng = EnsembleEngine(cfg, _behaviors(), n_lanes=len(seeds))
    st = eng.init_state()
    for lane, seed in enumerate(seeds):
        pos, dia, types, extra = _population(seed)
        st = eng.admit(st, lane, eng.stage_lane(pos, dia, types, extra,
                                                seed=seed))
    stats = eng.step(st).stats
    want = np.array([_plain_count(_population(s)[0], cfg, "streamed")
                     for s in seeds])
    got = np.stack([np.asarray(stats.sweep_slots),
                    np.asarray(stats.sweep_candidates)], axis=1)
    assert got.tolist() == want.tolist()
    # summed over lanes, as the benchmark reads a step's counters
    share = got[:, 1].sum() / got[:, 0].sum()
    assert share == pytest.approx(want[:, 1].sum() / want[:, 0].sum())


@pytest.mark.parametrize("dims", [(3, 4, 5), (1, 6, 2), (7, 1, 1)])
def test_touching_box_pairs_equals_a_loop_over_boxes(dims):
    counts = np.random.default_rng(sum(dims)).integers(0, 5, dims)
    want = 0
    for box in itertools.product(*map(range, dims)):
        for off in itertools.product((-1, 0, 1), repeat=3):
            nb = tuple(b + o for b, o in zip(box, off))
            if all(0 <= v < n for v, n in zip(nb, dims)):
                want += int(counts[box] * counts[nb])
    flat = counts.reshape(-1).astype(np.int16)   # the table, z fastest
    assert int(grid._touching_box_pairs(flat, dims)) == want
