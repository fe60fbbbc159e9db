"""The streamed sweep's window path (grid.resident_apply_fused, DESIGN.md
§3.2) against the per-row gather path and the O(N²) oracle.

A tile of T consecutive sorted rows reads each stencil column as one
contiguous window of W slots; a tile that holds a dead slot, or one of whose
windows would not fit, takes the per-row z-run gathers, and so do the rows
past the last full tile. Checked here:

  * the sweep, with each tile on the path its windows allow, gives the SIR
    exposure count and the force's neighbor count bit-exact, and the force
    within float tolerance, against the all-gather sweep and brute force,
    in a uniform population and in a clustered one where some tiles fall
    back, and in a pool with dead slots at its end and a partial last tile;
  * the same live agents in pools of two capacities get bit-identical
    results (what a capacity-ladder rewind needs);
  * tiles with a dead slot never take the window path.
"""

from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agents, grid as G
from repro.core.behaviors import INFECTED, Infection
from repro.core.forces import (FORCE_OUT_SPECS, FORCE_READS, ForceParams,
                               make_force_pair_fn)

SIDE, BOX = 48.0, 3.0


def _uniform(n, rng):
    return rng.uniform(0.5, SIDE - 0.5, (n, 3))


def _clustered(n, rng):
    # most agents packed into one x-slab: tiles in the sparse slabs next to
    # it span many keys, and their windows into the slab overflow W
    pos = rng.uniform(0.5, SIDE - 0.5, (n, 3))
    dense = rng.random(n) < 0.6
    pos[dense, 0] = rng.uniform(21.0, 24.0, dense.sum())
    return pos


POPULATIONS = {"uniform": _uniform, "clustered": _clustered}


def _sweep_setup(n, c, population, seed=0):
    rng = np.random.default_rng(seed)
    pos = POPULATIONS[population](n, rng).astype(np.float32)
    types = np.where(rng.random(n) < 0.1, INFECTED, 0).astype(np.int32)
    pool = agents.make_pool(
        c, position=jnp.asarray(pos),
        diameter=jnp.asarray(rng.uniform(2.0, 3.0, n).astype(np.float32)),
        agent_type=jnp.asarray(types))
    dims = (int(SIDE / BOX),) * 3
    spec = G.GridSpec(dims=dims, max_per_box=64)      # R = 192
    res = G.make_builder(spec)(pool, jnp.zeros(3), jnp.asarray(BOX))
    assert int(res.overflow) == 0                     # no z-run truncated
    ch = {k: v for k, v in res.pool.channels().items()
          if not k.startswith("extra.")}
    infection = Infection(radius=BOX).neighbor_kernels()[0]
    force = G.PairKernel("force", make_force_pair_fn(ForceParams()),
                         FORCE_OUT_SPECS, reads=FORCE_READS)
    return spec, res.grid, ch, res.pool.alive, [force, infection]


def _sweep(spec, grid, ch, alive, kernels, windows=True):
    if windows:
        return G.resident_apply_fused(spec, grid, ch, kernels, alive)
    orig = G._window_tables

    def no_window(*a, **k):
        lo, fits = orig(*a, **k)
        return lo, jnp.zeros_like(fits)

    with mock.patch.object(G, "_window_tables", no_window):
        return G.resident_apply_fused(spec, grid, ch, kernels, alive)


def _oracle(ch, alive, kernels):
    return {k.name: G.brute_force_apply(ch, alive, k.pair_fn, k.out_specs)
            for k in kernels}


def _assert_same(got, want, where, columns=True):
    # the exposure is an OR per call: summed over the 9 columns it counts
    # the columns with an infected neighbor, which one brute-force call
    # cannot; its threshold is what the behavior reads
    exposed = [np.asarray(r["infection"]["exposed"]) for r in (got, want)]
    if not columns:
        exposed = [e > 0 for e in exposed]
    np.testing.assert_array_equal(*exposed, err_msg=f"{where}: exposed")
    np.testing.assert_array_equal(np.asarray(got["force"]["force_nnz"]),
                                  np.asarray(want["force"]["force_nnz"]),
                                  err_msg=f"{where}: force_nnz")
    np.testing.assert_allclose(np.asarray(got["force"]["force"]),
                               np.asarray(want["force"]["force"]),
                               rtol=1e-5, atol=2e-6, err_msg=f"{where}: force")


@pytest.mark.parametrize("n,c", [(6000, 6144), (5000, 5400)],
                         ids=["full-tiles", "dead-tail-partial-tile"])
@pytest.mark.parametrize("population", sorted(POPULATIONS))
def test_window_path_matches_gathers_and_oracle(population, n, c):
    spec, grid, ch, alive, kernels = _sweep_setup(n, c, population)
    _, fits = G._window_tables(spec, grid, ch["position"])
    assert 0 < int(fits.sum()) < fits.size        # both paths engaged
    got = _sweep(spec, grid, ch, alive, kernels)
    _assert_same(got, _sweep(spec, grid, ch, alive, kernels, windows=False),
                 "vs gathers")
    _assert_same(got, _oracle(ch, alive, kernels), "vs brute force",
                 columns=False)
    assert int(jnp.sum(got["infection"]["exposed"] > 0)) > 0


def test_window_path_does_not_depend_on_the_capacity():
    n = 5000
    results = []
    for c in (5001, 7777):
        spec, grid, ch, alive, kernels = _sweep_setup(n, c, "clustered")
        res = _sweep(spec, grid, ch, alive, kernels)
        results.append({f"{k}.{o}": np.asarray(v)[:n]
                        for k, out in res.items() for o, v in out.items()})
    for name in results[0]:
        assert np.array_equal(results[0][name], results[1][name]), name


def test_tiles_with_dead_slots_take_the_gather_path():
    n, c = 5000, 5400
    spec, grid, ch, alive, kernels = _sweep_setup(n, c, "uniform")
    _, fits = G._window_tables(spec, grid, ch["position"])
    t = G.WINDOW_TILE
    for k, ok in enumerate(np.asarray(fits)):
        assert ok == ((k + 1) * t <= n), k      # a dead slot, and only it
