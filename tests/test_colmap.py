"""K1's column map as a capacity of its own: its width, its overflow flag
and demand, the capacity ladder's rung, and the SMEM slices at every width
the rung can reach (K1 in interpret mode)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CapacityExhausted, CapacityLadder, EngineConfig,
                        ForceParams, Simulation, morton)
from repro.core.behaviors import GrowDivide, RandomDeath, RandomWalk
from repro.core.engine import colmap_rung
from repro.kernels import collision_force as k1
from repro.kernels import ops

N = 32_768


def _oncology(width, n=N):
    """launch/simulate.py's oncology scenario and packing (seeds uniform in
    [0.35, 0.65] of the side, 9 um, overlapping by half) at ``n`` seeds,
    with the column map ``width`` wide: one pool slot per seed, since none
    divides for 21 steps."""
    side = max(160.0, n ** (1 / 3) * 16)
    cfg = EngineConfig(capacity=n, domain_lo=(0.0,) * 3,
                       domain_hi=(side,) * 3, interaction_radius=14.0, dt=0.2,
                       max_per_box=160, force_impl="pallas",
                       colmap_width=width,
                       force=ForceParams(max_displacement=1.0))
    behaviors = [GrowDivide(rate=0.7, threshold_diameter=12.0),
                 RandomWalk(sigma=0.1), RandomDeath(rate=0.012)]
    rng = np.random.default_rng(0)
    pos = rng.uniform(side * 0.35, side * 0.65, (n, 3)).astype(np.float32)
    return cfg, behaviors, pos, np.full(n, 9.0, np.float32)


def _plain_demand(pos, cfg):
    """The most 128-slot column blocks any 128-row block of the key-sorted
    pool reaches through its rows' 9 stencil z-runs, by a loop over rows."""
    dims = cfg.grid_spec.dims
    cell = np.asarray(morton.cell_of(jnp.asarray(pos), jnp.zeros(3),
                                     cfg.cell_size, dims))
    key = (cell[:, 0] * dims[1] + cell[:, 1]) * dims[2] + cell[:, 2]
    order = np.argsort(key, kind="stable")
    cell, key = cell[order], key[order]
    first = {}
    for i, k in enumerate(key):
        first.setdefault(k, i)
    counts = np.bincount(key, minlength=int(np.prod(dims)))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    worst = 0
    for r0 in range(0, len(key), k1.BLOCK):
        blocks = set()
        for x, y, z in cell[r0:r0 + k1.BLOCK]:
            for dx in (-1, 0, 1):
                for dy in (-1, 0, 1):
                    nx, ny = x + dx, y + dy
                    if not (0 <= nx < dims[0] and 0 <= ny < dims[1]):
                        continue
                    lo = (nx * dims[1] + ny) * dims[2] + max(z - 1, 0)
                    hi = (nx * dims[1] + ny) * dims[2] + min(z + 1,
                                                             dims[2] - 1)
                    s, e = starts[lo], starts[hi] + counts[hi]
                    if e > s:
                        blocks.update(range(s // k1.BLOCK,
                                            (e - 1) // k1.BLOCK + 1))
        worst = max(worst, len(blocks))
    return worst


def test_colmap_overflow_is_its_own_flag():
    """At simulate.py's packing a 16-block map is too narrow: the step
    reports it as colmap_overflow with the demand a plain count gives, not
    as a grid run overflow, and Simulation.run names the width."""
    cfg, behaviors, pos, dia = _oncology(16)
    sim = Simulation(cfg, behaviors)
    s0 = sim.init_state(pos, diameter=dia)
    stats = sim.step(s0).stats
    demand = _plain_demand(pos, cfg)
    assert demand > 16
    assert int(stats.colmap_overflow) == 1 and int(stats.box_overflow) == 0
    assert int(stats.colmap_demand) == demand
    assert stats.flags() == {"colmap_overflow": 1}
    with pytest.raises(RuntimeError, match="colmap_width"):
        sim.run(s0, 1, check_overflow=True)
    wide = Simulation(dataclasses.replace(cfg, colmap_width=demand),
                      behaviors).step(s0).stats
    assert not wide.flags() and int(wide.colmap_demand) == demand
    assert int(wide.k1_live_rows) == N
    assert int(wide.k1_tiles) > N // k1.BLOCK


def test_ladder_grows_the_colmap_width_bit_exact():
    """The ladder grows the width from the demand and re-runs the step: the
    trajectory is the bits of a run sized in advance."""
    cfg, behaviors, pos, dia = _oncology(16)
    ladder = CapacityLadder(cfg, behaviors)
    state = ladder.init_state(pos, diameter=dia, seed=3)
    for _ in range(3):
        state = ladder.step(state)
    grown = [r for r in ladder.rungs if r["field"] == "colmap_width"]
    assert grown and grown[0]["old"] == 16
    width = ladder.config.colmap_width
    assert width == grown[-1]["new"] == colmap_rung(
        cfg, _plain_demand(pos, cfg), ladder.ladder.growth_factor)
    sim = Simulation(dataclasses.replace(cfg, colmap_width=width), behaviors)
    ref = sim.init_state(pos, diameter=dia, seed=3)
    for _ in range(3):
        ref = sim.step(ref)
    for name, v in state.pool.channels().items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(ref.pool.channels()[name]),
                                      err_msg=name)


@pytest.mark.parametrize("run_capacity", [48, 192, 480, 3000])
def test_every_reachable_width_keeps_its_slice_in_smem(run_capacity):
    """From each default width, every rung the ladder can reach up to its
    ceiling gives launches whose table slice fits SMEM_TABLE_ENTRIES, on
    pools the kernel accepts at any capacity; past the ceiling the rung
    and the kernel refuse."""
    cfg = EngineConfig(capacity=1024, domain_lo=(0.0,) * 3,
                       domain_hi=(100.0,) * 3, interaction_radius=5.0,
                       max_per_run=run_capacity)
    width = cfg.colmap_capacity
    assert width == ops.default_colmap_width(run_capacity)
    widths = [width]
    while width < k1.SMEM_TABLE_ENTRIES:
        width = colmap_rung(dataclasses.replace(cfg, colmap_width=width),
                            width + 1, 2.0)
        widths.append(width)
    assert widths[-1] == k1.SMEM_TABLE_ENTRIES
    for w in widths:
        rows = k1.rows_per_call(w)
        assert 1 <= rows and rows * w <= k1.SMEM_TABLE_ENTRIES
        for capacity in (1000, 2048, 2_097_152, 1 << 22, 8_388_608):
            n_pad = k1.padded_size(capacity, w)
            n_rb = n_pad // k1.BLOCK
            assert n_pad >= capacity and n_pad % k1.BLOCK == 0
            assert n_rb <= rows or n_rb % rows == 0
            assert k1.padded_size(n_pad, w) == n_pad
    top = dataclasses.replace(cfg, colmap_width=k1.SMEM_TABLE_ENTRIES)
    with pytest.raises(CapacityExhausted):
        colmap_rung(top, k1.SMEM_TABLE_ENTRIES + 1, 2.0)
    with pytest.raises(ValueError, match="SMEM_TABLE_ENTRIES"):
        k1.rows_per_call(k1.SMEM_TABLE_ENTRIES + 1)


def test_default_width_follows_the_run_capacity():
    cfg, *_ = _oncology(None, n=4096)
    assert cfg.grid_spec.run_capacity == 480
    assert cfg.colmap_capacity == 18 * ops.colmap_span(480) == 90
    assert ops.colmap_span(480) == 480 // 128 + 1 + 1
    assert dataclasses.replace(cfg, colmap_width=40).colmap_capacity == 40


def _one_dense_run(max_per_run, n=1200):
    """``n`` agents packed into one grid box, so one 3-box z-run holds all
    of them: past the 897 agents a span of 8 column blocks covered, with
    the column map 16 wide (the run needs 10 blocks)."""
    cfg = EngineConfig(capacity=1280, domain_lo=(0.0,) * 3,
                       domain_hi=(70.0,) * 3, interaction_radius=14.0,
                       dt=0.2, max_per_run=max_per_run, force_impl="pallas",
                       colmap_width=16,
                       force=ForceParams(max_displacement=1.0))
    rng = np.random.default_rng(1)
    pos = rng.uniform(28.5, 41.5, (n, 3)).astype(np.float32)
    return cfg, pos, np.full(n, 4.0, np.float32)


def test_a_run_past_the_span_is_a_run_overflow_the_ladder_clears():
    """K1's span follows the run capacity: a run of 1,200 agents sized in
    advance flags nothing; from a run capacity of 256 it is a grid run
    overflow (named so), and the ladder grows max_per_run, not the width,
    to the bits of the run sized in advance."""
    assert ops.colmap_span(2048) > 8
    cfg, pos, dia = _one_dense_run(256)
    sim = Simulation(cfg)
    s0 = sim.init_state(pos, diameter=dia)
    stats = sim.step(s0).stats
    assert int(stats.box_overflow) == 1 and int(stats.colmap_overflow) == 1
    assert int(stats.colmap_demand) <= 16
    with pytest.raises(RuntimeError, match="max_per_run"):
        sim.run(s0, 1, check_overflow=True)

    ladder = CapacityLadder(cfg)
    state = ladder.init_state(pos, diameter=dia, seed=2)
    for _ in range(2):
        state = ladder.step(state)
    assert not state.stats.flags()
    assert {r["field"] for r in ladder.rungs} == {"max_per_run"}
    assert ladder.config.colmap_width == 16
    run = ladder.config.max_per_run
    assert run >= 1200

    sized = Simulation(dataclasses.replace(cfg, max_per_run=run))
    ref = sized.init_state(pos, diameter=dia, seed=2)
    for _ in range(2):
        ref = sized.step(ref)
        assert not ref.stats.flags()
    for name, v in state.pool.channels().items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(ref.pool.channels()[name]),
                                      err_msg=name)
