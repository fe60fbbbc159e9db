"""Resident grid layout + fused run-streaming force path (DESIGN.md §3.2).

Covers the PR-3 tentpole end to end:
  * run-streaming XLA forces (grid.resident_apply) vs the wide candidate
    matrix path and the O(N²) oracle, to the acceptance tolerance 2e-6;
  * the resident Pallas kernel (interpret mode, no sort/unsort) vs both;
  * block-granular query masking, including a capacity that is not a
    multiple of the chunk (the clamped trailing window);
  * box-granular static flags (conservative neighborhood wake-up);
  * the permutation–compaction composition under deaths and births mid-run;
  * engine-level detect_static on/off equivalence, XLA and Pallas, on a
    quiescent lattice with a churning (birth/death) active corner.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, ForceParams, Simulation, agents
from repro.core import compaction, grid as G, morton, statics
from repro.core.behaviors import GrowDivide, RandomDeath
from repro.core.forces import make_force_pair_fn
from repro.kernels import ops as kops

OUT_SPECS = {"force": ((3,), jnp.float32), "force_nnz": ((), jnp.int32)}


def _resident_setup(rng, n, c, dims, box, chunk):
    pos = rng.uniform(0.3, dims[0] * box - 0.3, (n, 3)).astype(np.float32)
    dia = rng.uniform(0.8, 1.6, (n,)).astype(np.float32)
    pool = agents.make_pool(c, position=jnp.asarray(pos),
                            diameter=jnp.asarray(dia))
    spec = G.GridSpec(dims=dims, max_per_box=c, max_per_run=c,
                      query_chunk=chunk)
    res = G.make_builder(spec, method="resident")(pool, jnp.zeros(3),
                                                   jnp.asarray(box))
    rpool, grid, order = res.pool, res.grid, res.order
    ch = {k: v for k, v in rpool.channels().items()
          if not k.startswith("extra.")}
    return pool, rpool, spec, grid, order, ch


@pytest.mark.parametrize("n,c,chunk", [(300, 384, 128), (333, 420, 128),
                                       (100, 100, 256)])
def test_resident_streaming_matches_oracle(rng, n, c, chunk):
    """Run-streaming forces == wide-matrix path == O(N²) oracle (≤2e-6)."""
    pool, rpool, spec, grid, order, ch = _resident_setup(
        rng, n, c, (8, 8, 8), 2.0, chunk)
    pair = make_force_pair_fn(ForceParams())

    res = G.resident_apply(spec, grid, ch, rpool.alive, pair, OUT_SPECS)
    # wide candidate-matrix path over the same resident pool
    wide = G.neighbor_apply(spec, grid, ch,
                            jnp.arange(c, dtype=jnp.int32), rpool.n_live,
                            pair, OUT_SPECS)
    oracle = G.brute_force_apply(ch, rpool.alive, pair, OUT_SPECS)

    assert float(jnp.max(jnp.abs(res["force"] - oracle["force"]))) <= 2e-6
    np.testing.assert_array_equal(np.asarray(res["force_nnz"]),
                                  np.asarray(oracle["force_nnz"]))
    assert float(jnp.max(jnp.abs(res["force"] - wide["force"]))) <= 2e-6


def test_resident_pallas_matches_streaming(rng):
    """Pallas resident core (no sort/unsort, interpret) vs run-streaming XLA,
    with a static fraction excluded at block granularity in both."""
    n, c = 320, 384
    pool, rpool, spec, grid, order, ch = _resident_setup(
        rng, n, c, (8, 8, 8), 2.5, 128)
    active = rpool.alive & jnp.asarray(rng.random(c) < 0.6)
    pair = make_force_pair_fn(ForceParams())

    f_k1, nnz_k1, ovf = kops.collision_force_resident(
        rpool.position, rpool.diameter, rpool.agent_type, rpool.alive,
        active, grid.starts, grid.counts, jnp.zeros(3), jnp.asarray(2.5),
        dims=spec.dims, k_rep=2.0, adhesion=None, adhesion_band=0.4)
    assert not bool(ovf.overflow)

    res = G.resident_apply(spec, grid, ch, active, pair, OUT_SPECS)
    np.testing.assert_allclose(np.asarray(f_k1), np.asarray(res["force"]),
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(nnz_k1),
                                  np.asarray(res["force_nnz"]))


def test_resident_query_mask_blocks(rng):
    """Masked resident_apply == full result restricted to the mask — even when
    the mask leaves whole blocks empty and capacity % chunk != 0."""
    n, c, chunk = 333, 333, 128
    pool, rpool, spec, grid, order, ch = _resident_setup(
        rng, n, c, (8, 8, 8), 2.0, chunk)
    pair = make_force_pair_fn(ForceParams())
    full = G.resident_apply(spec, grid, ch, rpool.alive, pair, OUT_SPECS)
    mask = rpool.alive & jnp.asarray(rng.random(c) < 0.3)
    # zero out whole blocks so the dynamic trip count actually shrinks
    mask = mask & (jnp.arange(c) // chunk != 1)
    part = G.resident_apply(spec, grid, ch, mask, pair, OUT_SPECS)
    for name in OUT_SPECS:
        want = jnp.where(mask.reshape((c,) + (1,) * (full[name].ndim - 1)),
                         full[name], 0)
        np.testing.assert_allclose(np.asarray(part[name]), np.asarray(want),
                                   atol=1e-6, err_msg=name)


def test_box_granular_statics_wake(rng):
    """A single disturbed agent wakes exactly its 3×3×3 box neighborhood."""
    # 4³ lattice of agents, one per box center, box size 2
    g = 4
    xs = np.stack(np.meshgrid(*[np.arange(g) * 2.0 + 1.0] * 3,
                              indexing="ij"), -1).reshape(-1, 3)
    n = len(xs)
    pool = agents.make_pool(n, position=jnp.asarray(xs, jnp.float32),
                            diameter=jnp.full((n,), 0.5))
    spec = G.GridSpec(dims=(g, g, g), max_per_box=n)
    res = G.make_builder(spec, method="resident")(pool, jnp.zeros(3),
                                                   jnp.asarray(2.0))
    rpool, grid, order = res.pool, res.grid, res.order
    # quiescent except one agent (in resident order, pick the slot in the
    # box at cell (2,2,2))
    moved = jnp.zeros((n,), bool)
    key_t = morton.linear_encode3(jnp.uint32(2), jnp.uint32(2), jnp.uint32(2),
                                  spec.dims)
    target = int(jnp.argmax(grid.keys == key_t))
    moved = moved.at[target].set(True)
    rpool = dataclasses.replace(rpool, moved=moved,
                                grew=jnp.zeros((n,), bool),
                                force_nnz=jnp.zeros((n,), jnp.int32))
    static = statics.update_static_flags(rpool, spec, grid, jnp.int32(5))
    cells = morton.cell_of(rpool.position, jnp.zeros(3), jnp.asarray(2.0),
                           spec.dims)
    dist = np.abs(np.asarray(cells) - np.asarray([2, 2, 2])).max(axis=1)
    awake = ~np.asarray(static)
    # inside the 3×3×3 neighborhood: awake; outside: static
    np.testing.assert_array_equal(awake, dist <= 1)


def test_permutation_composes_with_death_compaction(rng):
    """Deaths mid-run: one step later the live prefix is still in key order
    (the resident permutation subsumes compaction, stably)."""
    n = 400
    cfg = EngineConfig(capacity=512, domain_lo=(0, 0, 0),
                       domain_hi=(30, 30, 30), interaction_radius=3.0,
                       use_forces=False)
    sim = Simulation(cfg, [RandomDeath(rate=0.15)])
    pos = rng.uniform(0, 30, (n, 3)).astype(np.float32)
    st = sim.init_state(pos, diameter=np.full(n, 1.0, np.float32))
    st = sim.run(st, 6)
    nl = int(st.stats["n_live"])
    alive = np.asarray(st.pool.alive)
    assert 0 < nl < n
    assert alive[:nl].all() and not alive[nl:].any()
    keys = np.asarray(morton.linear_keys(
        st.pool.position, jnp.zeros(3),
        jnp.asarray(cfg.interaction_radius), sim.spec.dims))
    assert (np.diff(keys[:nl].astype(np.int64)) >= 0).all(), \
        "live prefix must stay grid-key sorted"


def test_permutation_composes_with_births(rng):
    """Births land at the tail; the live prefix before them stays key-sorted
    (positions are static in this config, so survivor keys are unchanged)."""
    cfg = EngineConfig(capacity=1024, domain_lo=(0, 0, 0),
                       domain_hi=(60, 60, 60), interaction_radius=6.0,
                       use_forces=False, dt=0.5)
    sim = Simulation(cfg, [GrowDivide(rate=1.0, threshold_diameter=10.0)])
    pos = rng.uniform(5, 55, (200, 3)).astype(np.float32)
    st = sim.init_state(pos, diameter=np.full(200, 8.0, np.float32))
    st = sim.run(st, 4)
    nl = int(st.stats["n_live"])
    births_last = int(st.stats["births"])
    assert nl > 200 and births_last > 0
    alive = np.asarray(st.pool.alive)
    assert alive[:nl].all() and not alive[nl:].any()
    keys = np.asarray(morton.linear_keys(
        st.pool.position, jnp.zeros(3),
        jnp.asarray(cfg.interaction_radius), sim.spec.dims))
    sorted_upto = nl - births_last
    assert (np.diff(keys[:sorted_upto].astype(np.int64)) >= 0).all()


def _churn_sim(detect_static, force_impl):
    cfg = EngineConfig(capacity=1024, domain_lo=(0, 0, 0),
                       domain_hi=(48, 48, 48), interaction_radius=6.0,
                       dt=0.1, detect_static=detect_static,
                       force_impl=force_impl, max_per_box=64,
                       query_chunk=128,
                       force=ForceParams(max_displacement=0.5))
    # quiescent lattice (spacing 6 > max interaction distance 2.4): zero
    # force either way, so skipping it is exact
    xs = np.stack(np.meshgrid(*[np.arange(6) * 6.0 + 6.0] * 3,
                              indexing="ij"), -1).reshape(-1, 3)
    types = np.zeros(len(xs), np.int32)
    # churning corner: tight cluster that divides and dies
    m = 24
    rng = np.random.default_rng(11)
    corner = rng.uniform(2.0, 8.0, (m, 3))
    pos = np.concatenate([xs, corner]).astype(np.float32)
    types = np.concatenate([types, np.ones(m, np.int32)])
    dia = np.concatenate([np.full(len(xs), 2.0), np.full(m, 4.8)]
                         ).astype(np.float32)
    sim = Simulation(cfg, [GrowDivide(rate=2.0, threshold_diameter=5.0,
                                      applies_to=1),
                           RandomDeath(rate=0.05, applies_to=1)])
    st = sim.init_state(pos, diameter=dia, agent_type=types)
    return sim, st


@pytest.mark.parametrize("force_impl", ["xla", "pallas"])
def test_detect_static_equivalent_under_churn(force_impl):
    """detect_static on/off must not change the dynamics — including through
    births and deaths that exercise the permutation–compaction composition —
    while actually skipping work (n_active < n_live)."""
    finals = {}
    for ds in (False, True):
        sim, st = _churn_sim(ds, force_impl)
        saw_birth = saw_death = False
        for _ in range(8):
            st = sim.step(st)
            saw_birth |= int(st.stats["births"]) > 0
            saw_death |= int(st.stats["deaths"]) > 0
        finals[ds] = st
        assert saw_birth and saw_death, "churn must actually churn"
    n_live = int(finals[True].stats["n_live"])
    assert n_live == int(finals[False].stats["n_live"])
    # identical dynamics → identical resident layouts → per-slot comparable
    np.testing.assert_allclose(
        np.asarray(finals[True].pool.position[:n_live]),
        np.asarray(finals[False].pool.position[:n_live]), atol=1e-5)
    # and the static machinery did skip something: lattice ≫ corner
    assert int(finals[True].stats["n_active"]) < n_live
