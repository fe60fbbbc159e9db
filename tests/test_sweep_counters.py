"""The fused sweep's work counters (StepStats ``sweep_*``) against a plain
numpy count.

One SIR step (Infection alone: no forces, no walk) of a population of 4,096,
in the streamed and in the pair-list mode of grid.resident_apply_fused.
Capacity equals the population, so every row is a live agent and every
query tile or block is visited. Streamed, the sweep takes the pool in tiles
of T = grid.WINDOW_TILE rows; a tile takes the window path when, for each of
the 9 stencil columns, the sorted agents whose boxes lie between its rows'
lowest and highest z-run keys, shifted to that column, number at most
W = grid.WINDOW, and the per-row gathers of width R otherwise. So
``sweep_rows`` is the population, ``sweep_window_rows`` the rows of the
tiles that fit, and ``sweep_slots`` 9·W lanes a row of those and 9·R of the
others (a clustered population has both);
``sweep_candidates`` is the number of (agent, other agent) pairs whose grid
boxes touch (the 3×3×3 boxes around the agent's own), or, from the pair
list, those of them within the list's radius, at most ``max_pairs`` an
agent, over P lanes a row. A step that runs no fused sweep leaves all at 0.
Lanes of the ensemble count their own populations, so summed over lanes
the counters and their ratios stay those of the whole sweep.
"""

import itertools

import numpy as np
import pytest

from repro.core import (EngineConfig, EnsembleEngine, Simulation, StepStats,
                        grid)
from repro.core.behaviors import INFECTED, Infection

N, SIDE, CHUNK, MAX_PAIRS = 4096, 64.0, 256, 48


def _cfg(mode):
    return EngineConfig(
        capacity=N, domain_lo=(0.0,) * 3, domain_hi=(SIDE,) * 3,
        interaction_radius=3.0, use_forces=False, max_per_box=32,
        query_chunk=CHUNK, fused_sweep=mode != "unfused",
        pairlist=(grid.PairListConfig(skin=0.0, max_pairs=MAX_PAIRS)
                  if mode == "pairlist" else None))


def _population(seed, clustered=False):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.5, SIDE - 0.5, (N, 3)).astype(np.float32)
    if clustered:       # 60% in one x-slab of boxes, the rest around it
        dense = rng.random(N) < 0.6
        pos[dense, 0] = rng.uniform(30.5, 32.5, dense.sum())
    types = np.where(np.arange(N) < N // 10, INFECTED, 0).astype(np.int32)
    return (pos, np.ones(N, np.float32), types,
            {"infect_timer": np.full(N, 8, np.int32)})


def _behaviors():
    return [Infection(radius=3.0, beta=0.5, recovery_time=8)]


def _window_rows(cell, dims):
    """Rows of the full tiles of the key-sorted cells each of whose column
    windows spans at most W agents (in a population of at least W)."""
    t, w = grid.WINDOW_TILE, grid.WINDOW
    nx, ny, nz = dims
    own = cell[:, 0] * ny * nz + cell[:, 1] * nz
    keys = own + cell[:, 2]
    lo_key = own + np.maximum(cell[:, 2] - 1, 0)
    hi_key = own + np.minimum(cell[:, 2] + 1, nz - 1)
    rows = 0
    for first in range(0, len(keys) - t + 1, t):
        fits = True
        for dx, dy in itertools.product((-1, 0, 1), repeat=2):
            shift = (dx * ny + dy) * nz
            lo = lo_key[first:first + t].min() + shift
            hi = hi_key[first:first + t].max() + shift
            span = (np.searchsorted(keys, min(hi, nx * ny * nz - 1),
                                    "right")
                    - np.searchsorted(keys, max(lo, 0)))
            if hi >= 0 and lo < nx * ny * nz:
                fits &= span <= w
        rows += t * (fits and len(keys) >= w)
    return rows


def _plain_count(pos, cfg, mode):
    """(slots, candidates, rows, window rows) of one sweep over every
    agent, by numpy."""
    dims = np.asarray(cfg.grid_spec.dims)
    rel = (pos - np.float32(0.0)) / np.float32(cfg.cell_size)
    cell = np.clip(np.floor(rel).astype(np.int64), 0, dims - 1)
    near = (np.abs(cell[:, None, :] - cell[None, :, :]) <= 1).all(-1)
    np.fill_diagonal(near, False)
    if mode == "unfused":
        return 0, 0, 0, 0
    if mode == "streamed":
        order = np.argsort((cell[:, 0] * dims[1] + cell[:, 1]) * dims[2]
                           + cell[:, 2], kind="stable")
        window_rows = _window_rows(cell[order], dims)
        slots = 9 * (grid.WINDOW * window_rows
                     + cfg.grid_spec.run_capacity * (N - window_rows))
        return slots, int(near.sum()), N, window_rows
    i, j = np.nonzero(near)
    d = pos[j] - pos[i]
    in_reach = (np.sum(d * d, axis=-1)
                <= np.square(np.float32(cfg.interaction_radius)))
    per_row = np.bincount(i[in_reach], minlength=N)
    return (N * MAX_PAIRS, int(np.minimum(per_row, MAX_PAIRS).sum()), N, 0)


def _counts(stats):
    return tuple(int(stats[f]) for f in StepStats.SWEEP_FIELDS)


@pytest.mark.parametrize("mode", ["streamed", "pairlist", "unfused"])
def test_sweep_counters_equal_a_plain_count(mode):
    pos, dia, types, extra = _population(5)
    cfg = _cfg(mode)
    sim = Simulation(cfg, _behaviors())
    stats = sim.step(sim.init_state(pos, diameter=dia, agent_type=types,
                                    extra_init=extra)).stats
    assert not stats.flags()                   # no z-run or list truncated
    assert _counts(stats) == _plain_count(pos, cfg, mode)


@pytest.mark.parametrize("clustered", [False, True],
                         ids=["uniform", "clustered"])
def test_sweep_window_rows_equal_a_plain_count(clustered):
    pos, dia, types, extra = _population(7, clustered)
    cfg = _cfg("streamed")
    sim = Simulation(cfg, _behaviors())
    stats = sim.step(sim.init_state(pos, diameter=dia, agent_type=types,
                                    extra_init=extra)).stats
    assert not stats.flags()
    got = _counts(stats)
    assert got == _plain_count(pos, cfg, "streamed")
    # uniform: every tile on the window path; clustered: not those beside
    # the dense slab
    assert (got[3] == N) != clustered and got[3] > 0


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
    got = np.stack([np.asarray(stats[f]) for f in StepStats.SWEEP_FIELDS],
                   axis=1)
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
