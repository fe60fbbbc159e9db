"""Cross-environment force parity: every neighbor environment must agree.

One randomized agent cloud, six force paths: uniform grid (wide candidate
matrix), the resident run-streaming loop (grid.make_builder('resident') +
grid.resident_apply — the engine's hot path), uniform grid via the Pallas K1
kernel (interpret mode), scatter-table grid, hash grid (streamed probes), and
the exact O(N²) brute-force oracle. All must agree within tolerance —
including on an *anisotropic* domain, which exercises the exact-size
``prod(dims)`` table (a Morton-padded table would index out of its real box
range there; DESIGN.md §3).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agents, grid as G
from repro.core.forces import ForceParams, make_force_pair_fn
from repro.kernels import ops as kops

OUT_SPECS = {"force": ((3,), jnp.float32), "force_nnz": ((), jnp.int32)}


def _cloud(rng, n, lo, hi):
    lo = np.asarray(lo, np.float32)
    hi = np.asarray(hi, np.float32)
    pos = rng.uniform(lo + 0.5, hi - 0.5, (n, 3)).astype(np.float32)
    dia = rng.uniform(0.8, 1.4, (n,)).astype(np.float32)
    return pos, dia


def _forces_all_envs(pool, spec, radius, channels, pair):
    c = pool.capacity
    all_idx = jnp.arange(c, dtype=jnp.int32)
    n_q = jnp.int32(c)
    origin = jnp.zeros(3)
    r = jnp.asarray(radius)
    out = {}

    sres = G.make_builder(spec, method="sorted")(pool, origin, r)
    gs = sres.grid
    assert int(sres.overflow) == 0
    out["uniform"] = G.neighbor_apply(spec, gs, channels, all_idx, n_q,
                                      pair, OUT_SPECS)
    # resident run-streaming path (the engine's hot path): permutes the pool
    # into grid order; map the forces back to slot order for comparison
    rres = G.make_builder(spec, method="resident")(pool, origin, r)
    rpool, rgs, order = rres.pool, rres.grid, rres.order
    rch = {k: v for k, v in rpool.channels().items()
           if not k.startswith("extra.")}
    res = G.resident_apply(spec, rgs, rch, rpool.alive, pair, OUT_SPECS)
    out["uniform_resident"] = {
        name: jnp.zeros_like(val).at[order].set(val)
        for name, val in res.items()}

    sg = G.make_builder(spec, method="scatter")(pool, origin, r).grid
    hg = G.make_builder(spec, method="hash")(pool, origin, r).grid

    def cf(q_pos, q_slot):
        ids, valid = G.scatter_grid_candidates(spec, sg, q_pos)
        valid &= ids != q_slot[:, None]
        return ids, valid
    out["scatter"] = G.chunk_apply(channels, channels, all_idx, n_q, cf,
                                   pair, OUT_SPECS, spec.query_chunk)

    def hash_phase(q_pos, q_slot, j):
        ids, valid = G.hash_grid_probe(spec, hg, q_pos, j)
        valid &= ids != q_slot[:, None]
        return ids, valid
    out["hash"] = G.phased_chunk_apply(channels, channels, all_idx, n_q,
                                       hash_phase, 27, pair, OUT_SPECS,
                                       spec.query_chunk)

    out["brute"] = G.brute_force_apply(channels, pool.alive, pair, OUT_SPECS)
    return out


@pytest.mark.parametrize("domain,dims,n", [
    ((16.0, 16.0, 16.0), (8, 8, 8), 300),
    ((40.0, 16.0, 8.0), (20, 8, 4), 350),     # anisotropic: non-cubic table
])
def test_all_environments_agree(rng, domain, dims, n):
    radius = 2.0
    pos, dia = _cloud(rng, n, (0, 0, 0), domain)
    pool = agents.make_pool(n, position=jnp.asarray(pos),
                            diameter=jnp.asarray(dia))
    spec = G.GridSpec(dims=dims, max_per_box=n, max_per_run=n, query_chunk=128)
    assert spec.table_size == dims[0] * dims[1] * dims[2]   # no pow2 padding
    channels = {k: v for k, v in pool.channels().items()
                if not k.startswith("extra.")}
    pair = make_force_pair_fn(ForceParams())
    res = _forces_all_envs(pool, spec, radius, channels, pair)

    ref = np.asarray(res["brute"]["force"])
    for name in ("uniform", "uniform_resident", "scatter", "hash"):
        np.testing.assert_allclose(np.asarray(res[name]["force"]), ref,
                                   atol=1e-4, err_msg=name)
        np.testing.assert_array_equal(np.asarray(res[name]["force_nnz"]),
                                      np.asarray(res["brute"]["force_nnz"]),
                                      err_msg=name)


def test_hash_bucket_collision_no_double_count():
    """Two stencil cells hashing to one bucket must not double-count it.

    Cells (34,129,23) and (35,128,21) collide into bucket 7476 under the
    3-prime hash with 2^14 buckets, and *both* lie in the stencil of a query
    in cell (34,128,22) — without the cell_keys re-check the neighbor's
    bucket is gathered once per colliding stencil cell, doubling its force
    and force_nnz. Needs grid coords ≥ ~130, which the 33³ parity grids
    never reach.
    """
    dims = (40, 132, 25)
    radius = 4.0
    # q at the center of cell (34,128,22); nbr in cell (34,129,23) within
    # contact distance (diameters 4 → contact at dist < 4)
    pos = np.asarray([[138.0, 514.0, 90.0],
                      [138.5, 516.5, 92.5]], np.float32)
    dia = np.full((2,), 4.0, np.float32)
    pool = agents.make_pool(2, position=jnp.asarray(pos),
                            diameter=jnp.asarray(dia))
    spec = G.GridSpec(dims=dims, max_per_box=4, max_per_run=8, query_chunk=2)
    channels = {k: v for k, v in pool.channels().items()
                if not k.startswith("extra.")}
    pair = make_force_pair_fn(ForceParams())
    hg = G.make_builder(spec, method="hash")(pool, jnp.zeros(3),
                                              jnp.asarray(radius)).grid
    assert int(hg.keys[0]) != int(hg.keys[1])   # distinct buckets for agents

    def hash_phase(q_pos, q_slot, j):
        ids, valid = G.hash_grid_probe(spec, hg, q_pos, j)
        valid &= ids != q_slot[:, None]
        return ids, valid
    all_idx = jnp.arange(2, dtype=jnp.int32)
    res = G.phased_chunk_apply(channels, channels, all_idx, jnp.int32(2),
                               hash_phase, 27, pair, OUT_SPECS,
                               spec.query_chunk)
    ref = G.brute_force_apply(channels, pool.alive, pair, OUT_SPECS)
    np.testing.assert_array_equal(np.asarray(res["force_nnz"]),
                                  np.asarray(ref["force_nnz"]))
    np.testing.assert_allclose(np.asarray(res["force"]),
                               np.asarray(ref["force"]), atol=1e-4)


@pytest.mark.parametrize("dims,domain", [
    ((8, 8, 8), (16.0, 16.0, 16.0)),
    ((20, 8, 4), (40.0, 16.0, 8.0)),          # anisotropic linear-key table
])
def test_pallas_collision_matches_xla_grid(rng, dims, domain):
    """K1 kernel (linear-key column map, interpret mode) vs the XLA grid path."""
    n, c = 260, 384
    box = 2.0
    pos, _ = _cloud(rng, n, (0, 0, 0), domain)
    dia = rng.uniform(0.5, 1.4, (n,)).astype(np.float32)
    P = np.zeros((c, 3), np.float32); P[:n] = pos
    D = np.zeros((c,), np.float32); D[:n] = dia
    alive = np.zeros((c,), bool); alive[:n] = True
    pool = agents.make_pool(c, position=jnp.asarray(pos),
                            diameter=jnp.asarray(dia))
    pool = dataclasses.replace(pool, alive=jnp.asarray(alive))

    f_k1, nnz_k1, ovf = kops.collision_force(
        jnp.asarray(P), jnp.asarray(D), jnp.zeros((c,), jnp.int32),
        jnp.asarray(alive), jnp.asarray(alive), jnp.zeros(3),
        jnp.asarray(box), dims=dims, k_rep=2.0, adhesion=None,
        adhesion_band=0.4)
    assert not bool(ovf)

    spec = G.GridSpec(dims=dims, max_per_box=c, query_chunk=128)
    gs = G.make_builder(spec, method="sorted")(pool, jnp.zeros(3),
                                                jnp.asarray(box)).grid
    channels = {k: v for k, v in pool.channels().items()
                if not k.startswith("extra.")}
    pair = make_force_pair_fn(ForceParams())
    res = G.neighbor_apply(spec, gs, channels,
                           jnp.arange(c, dtype=jnp.int32), pool.n_live,
                           pair, OUT_SPECS)
    np.testing.assert_allclose(np.asarray(f_k1), np.asarray(res["force"]),
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nnz_k1),
                                  np.asarray(res["force_nnz"]))
