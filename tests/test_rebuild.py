"""Counting-sort build + RebuildPolicy(every_k): the unified builder surface.

Three contracts from DESIGN.md §2/§4:

  * the O(N) counting-sort permutation (host callback and in-graph radix) is
    **bit-exact** with the stable ``jnp.argsort`` it replaces — the stable
    (key, slot) order is unique, so every impl must produce the same int32
    permutation on every key distribution, including all-dead and
    single-box degenerate ones;
  * ``make_builder`` is the one grid-build entry point: every method shares
    the BuildResult overflow/demand surface (§4.2 never-silent), and the
    legacy ``build_*`` zoo warns ``GridBuilderDeprecationWarning`` for one
    release;
  * ``RebuildPolicy(mode='every_k')`` may *skip* builds only when the skip
    is invisible: forces-only runs must match the every-step schedule to
    float tolerance while actually skipping, structural churn (births)
    must force a rebuild on the next step, the capacity ladder's rewind
    must stay bit-exact while a cached build is live, and the 4-shard
    distributed engine must skip (ghost/migration-clean slabs only) with
    exact parity.

Parity runs use forces-only dynamics with identities stored in
``agent_type``: behaviors draw per-slot randomness, so any schedule that
changes the resident permutation re-deals their noise — only deterministic,
slot-independent dynamics isolate the rebuild schedule under test. Configs
keep ``interaction_radius ≥ max diameter + adhesion_band`` so the grid
stencil covers every interacting pair (the §3.1 exactness contract).
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agents, engine, grid as G
from repro.core.behaviors import GrowDivide


# ---------------------------------------------------------------------------
# counting sort: every impl bit-exact with the stable-argsort oracle
# ---------------------------------------------------------------------------

TABLE = 9 * 9 * 9               # non-power-of-two linear key domain
# one, two and three radix passes (11-bit digits): 729 boxes, the oncology
# cell's ~117³ and the epidemiology cell's 170³
TABLES = (TABLE, 117 ** 3, 170 ** 3)
_DEAD = np.uint32(0xFFFFFFFF)   # morton.DEAD_KEY


def _oracle(keys):
    return np.argsort(keys, kind="stable").astype(np.int32)


def _key_cases(rng, c, table=TABLE):
    uniform = rng.integers(0, table, c).astype(np.uint32)
    mixed = uniform.copy()
    mixed[rng.random(c) < 0.3] = _DEAD
    clustered = rng.choice(
        np.asarray([0, 5, table - 1], np.uint32), c).astype(np.uint32)
    return {"uniform": uniform,
            "uniform_with_dead": mixed,
            "clustered": clustered,
            "all_dead": np.full(c, _DEAD, np.uint32),
            "single_box": np.zeros(c, np.uint32)}


@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("impl", ["host", "xla", "auto", "argsort"])
def test_counting_sort_bit_exact(rng, impl, table):
    # sizes below / far below / at / just past the radix block (1024), and
    # many blocks
    for c in (1, 7, 1024, 1359, 70_000):
        for name, keys in _key_cases(rng, c, table).items():
            order = np.asarray(G.counting_sort_order(
                jnp.asarray(keys), table, impl=impl))
            assert order.dtype == np.int32, (impl, name, c)
            assert np.array_equal(order, _oracle(keys)), (impl, name, c)
    # under vmap (the ensemble's lane axis): two lanes, each its own keys
    cases = _key_cases(rng, 1359, table)
    lanes = np.stack([cases["uniform_with_dead"], cases["clustered"]])
    order = np.asarray(jax.vmap(lambda k: G.counting_sort_order(
        k, table, impl=impl))(jnp.asarray(lanes)))
    assert np.array_equal(order, np.stack([_oracle(k) for k in lanes])), impl


def _box_cases(rng):
    """(sorted keys, table size) with the layouts box_tables must read."""
    m = 17 ** 3
    uniform = rng.integers(0, m, 5000).astype(np.uint32)
    uniform[-1200:] = _DEAD
    inner = rng.integers(1, m - 1, 3000).astype(np.uint32)
    return {"uniform_dead_tail": (np.sort(uniform), m),
            "first_and_last_empty": (np.sort(inner), m),
            "all_dead": (np.full(2048, _DEAD, np.uint32), m),
            "one_box": (np.asarray([0] * 40 + [_DEAD] * 9, np.uint32), 1),
            "int32_counts": (np.sort(rng.integers(0, 729, 1 << 15)
                                     .astype(np.uint32)), 729)}


@pytest.mark.parametrize("case", ["uniform_dead_tail", "first_and_last_empty",
                                  "all_dead", "one_box", "int32_counts"])
def test_box_tables_match_searchsorted(rng, case):
    keys, m = _box_cases(rng)[case]
    starts, counts = G.box_tables(jnp.asarray(keys), m)
    bounds = np.searchsorted(keys, np.arange(m + 1, dtype=np.uint32), "left")
    assert starts.dtype == np.int32
    assert counts.dtype == (np.int32 if len(keys) >= 1 << 15 else np.int16)
    assert np.array_equal(np.asarray(starts), bounds[:-1])
    assert np.array_equal(np.asarray(counts), np.diff(bounds))


def test_counting_sort_rejects_unknown_impl():
    with pytest.raises(ValueError, match="sort_impl"):
        G.counting_sort_order(jnp.zeros(4, jnp.uint32), TABLE, impl="quick")


# ---------------------------------------------------------------------------
# make_builder: one entry point, common overflow surface, deprecation shims
# ---------------------------------------------------------------------------

def _one_box_pool(rng, n=100, c=128):
    # every agent in grid box (0,0,0) → demand == n for every structure
    pos = rng.uniform(0.0, 0.9, (n, 3)).astype(np.float32)
    return agents.make_pool(c, position=jnp.asarray(pos))


@pytest.mark.parametrize("method", sorted(G.BUILD_METHODS))
def test_make_builder_common_overflow_surface(rng, method):
    pool = _one_box_pool(rng)
    spec = G.GridSpec(dims=(8, 8, 8), max_per_box=8)
    res = G.make_builder(spec, method=method)(pool, jnp.zeros(3),
                                              jnp.asarray(2.0))
    assert isinstance(res, G.BuildResult)
    assert int(res.demand) == 100
    cap = {"resident": spec.run_capacity, "sorted": spec.run_capacity,
           "scatter": spec.max_per_box,
           "hash": G.HASH_K_MULT * spec.max_per_box}[method]
    assert int(res.overflow) == max(100 - cap, 0), method
    order = np.asarray(res.order)
    assert np.array_equal(np.sort(order), np.arange(pool.capacity)), method
    if method != "resident":
        # only the resident build permutes the pool itself
        assert np.array_equal(order, np.arange(pool.capacity))
        assert res.pool is pool


def test_make_builder_rejects_unknown_knobs():
    spec = G.GridSpec(dims=(4, 4, 4))
    with pytest.raises(ValueError, match="method"):
        G.make_builder(spec, method="voxel")
    with pytest.raises(ValueError, match="sort_impl"):
        G.make_builder(spec, sort_impl="quick")


def test_deprecated_builders_warn_and_match(rng):
    pos = rng.uniform(0.0, 15.9, (60, 3)).astype(np.float32)
    pool = agents.make_pool(64, position=jnp.asarray(pos))
    spec = G.GridSpec(dims=(8, 8, 8), max_per_box=64)
    origin, bs = jnp.zeros(3), jnp.asarray(2.0)

    def same(a, b):
        fa, fb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(fa) == len(fb)
        for x, y in zip(fa, fb):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    with pytest.warns(G.GridBuilderDeprecationWarning, match="make_builder"):
        legacy = G.build(spec, pool, origin, bs)
    same(legacy, G.make_builder(spec, method="sorted")(pool, origin, bs).grid)

    with pytest.warns(G.GridBuilderDeprecationWarning, match="make_builder"):
        rpool, rgrid, rorder = G.build_resident(spec, pool, origin, bs)
    res = G.make_builder(spec, method="resident")(pool, origin, bs)
    same((rpool.channels(), rgrid, rorder),
         (res.pool.channels(), res.grid, res.order))

    with pytest.warns(G.GridBuilderDeprecationWarning, match="make_builder"):
        sg = G.build_scatter_grid(spec, pool, origin, bs)
    same(sg, G.make_builder(spec, method="scatter")(pool, origin, bs).grid)

    with pytest.warns(G.GridBuilderDeprecationWarning, match="make_builder"):
        hg = G.build_hash_grid(spec, pool, origin, bs)
    same(hg, G.make_builder(spec, method="hash")(pool, origin, bs).grid)


# ---------------------------------------------------------------------------
# RebuildPolicy / EngineConfig validation: knob-named errors
# ---------------------------------------------------------------------------

def test_rebuild_policy_validation():
    with pytest.raises(ValueError, match="rebuild.mode"):
        G.RebuildPolicy(mode="sometimes")
    with pytest.raises(ValueError, match="rebuild.k"):
        G.RebuildPolicy(mode="every_k", k=0, displacement_bound=1.0)
    with pytest.raises(ValueError, match="rebuild.displacement_bound"):
        G.RebuildPolicy(mode="every_k", k=2, displacement_bound=-1.0)
    with pytest.raises(ValueError, match="every_step"):
        G.RebuildPolicy(k=3)                 # knobs without opting in
    assert G.RebuildPolicy().cell_slack == 0.0
    pol = G.RebuildPolicy(mode="every_k", k=4, displacement_bound=1.5)
    assert pol.cell_slack == 1.5


_BASE = dict(capacity=64, domain_lo=(0., 0., 0.), domain_hi=(16.,) * 3,
             interaction_radius=2.0)
_POL = G.RebuildPolicy(mode="every_k", k=4, displacement_bound=1.0)


def test_engine_config_rebuild_validation():
    with pytest.raises(ValueError, match="uniform_grid"):
        engine.EngineConfig(**_BASE, environment="hash_grid", rebuild=_POL)
    with pytest.raises(ValueError, match="detect_static"):
        engine.EngineConfig(**_BASE, detect_static=True, rebuild=_POL)
    with pytest.raises(ValueError, match="sort_impl"):
        engine.EngineConfig(**_BASE, sort_impl="quick")
    # the displacement bound widens the grid cells (coverage argument)
    cfg = engine.EngineConfig(**_BASE, rebuild=_POL)
    assert cfg.cell_size == 3.0
    assert engine.EngineConfig(**_BASE).cell_size == 2.0


def test_dist_config_surfaces_rebuild_identically():
    from repro.core import distributed
    # same knob-named error through the DistConfig path ...
    with pytest.raises(ValueError, match="detect_static"):
        distributed.DistConfig(
            engine=engine.EngineConfig(**_BASE, detect_static=True,
                                       rebuild=_POL),
            n_shards=2, local_capacity=64, halo_capacity=16,
            migrate_capacity=16)
    # ... and the halo widens by the same cell slack the grid uses
    mk = lambda cfg: distributed.DistConfig(
        engine=cfg, n_shards=2, local_capacity=64, halo_capacity=16,
        migrate_capacity=16)
    plain = mk(engine.EngineConfig(**_BASE))
    cached = mk(engine.EngineConfig(**_BASE, rebuild=_POL))
    assert cached.halo_width == plain.halo_width + _POL.displacement_bound


# ---------------------------------------------------------------------------
# every_k skip parity: single device
# ---------------------------------------------------------------------------

def _forces_cfg(side, rebuild=None, capacity=512):
    kw = dict(capacity=capacity, domain_lo=(0., 0., 0.),
              domain_hi=(side,) * 3, interaction_radius=3.0,
              use_forces=True, max_per_box=32)
    if rebuild is not None:
        kw["rebuild"] = rebuild
    return engine.EngineConfig(**kw)


def _live_by_id(st):
    a = np.asarray(st.pool.alive)
    p = np.asarray(st.pool.position)[a]
    return p[np.argsort(np.asarray(st.pool.agent_type)[a])]


def test_every_k_skips_and_matches_every_step(rng):
    SIDE, N = 24.0, 400
    pos = rng.uniform(1.0, SIDE - 1.0, (N, 3)).astype(np.float32)
    dia = np.full((N,), 2.2, np.float32)
    ids = np.arange(N, dtype=np.int32)          # persistent identity

    pol = G.RebuildPolicy(mode="every_k", k=4, displacement_bound=1.0)
    sim_a = engine.Simulation(_forces_cfg(SIDE), behaviors=[])
    sim_b = engine.Simulation(_forces_cfg(SIDE, pol), behaviors=[])
    sa = sim_a.init_state(jnp.asarray(pos), jnp.asarray(dia), jnp.asarray(ids))
    sb = sim_b.init_state(jnp.asarray(pos), jnp.asarray(dia), jnp.asarray(ids))

    steps, rebuilds = 20, 0
    for _ in range(steps):
        sa, sb = sim_a.step(sa), sim_b.step(sb)
        assert int(sa.stats["rebuilds"]) == 1    # every_step never skips
        assert int(sb.stats["rebuilds"]) in (0, 1)   # rebuilt or skipped
        rebuilds += int(sb.stats["rebuilds"])
    skips = steps - rebuilds
    assert skips > 0, "quiescent forces-only run produced zero skips"
    assert int(sa.stats["n_live"]) == int(sb.stats["n_live"]) == N
    d = float(np.abs(_live_by_id(sa) - _live_by_id(sb)).max())
    # stale-superset candidates contribute exactly zero force; the residue
    # is float summation-order noise only
    assert d < 1e-3, d


def test_births_force_rebuild_next_step(rng):
    SIDE, N = 24.0, 64
    pos = rng.uniform(2.0, SIDE - 2.0, (N, 3)).astype(np.float32)
    dia = np.full((N,), 2.8, np.float32)         # near division threshold
    # generous budget: only structural dirt may force a rebuild
    pol = G.RebuildPolicy(mode="every_k", k=64, displacement_bound=100.0)
    sim = engine.Simulation(
        _forces_cfg(SIDE, pol, capacity=1024),
        behaviors=[GrowDivide(rate=0.5, threshold_diameter=3.0)])
    st = sim.init_state(jnp.asarray(pos), jnp.asarray(dia))
    births, rebuilds = [], []
    for _ in range(8):
        st = sim.step(st)
        births.append(int(st.stats["births"]))
        rebuilds.append(int(st.stats["rebuilds"]))
    assert rebuilds[0] == 1                      # fresh state builds
    for t in range(len(births) - 1):
        if births[t] > 0:
            assert rebuilds[t + 1] == 1, (t, births, rebuilds)
    assert sum(births) > 0, "scenario produced no births"


# ---------------------------------------------------------------------------
# capacity ladder under every_k: rewind stays bit-exact with a live cache
# ---------------------------------------------------------------------------

def test_ladder_every_k_bit_exact(rng):
    SIDE, N = 24.0, 48
    pos = rng.uniform(2.0, SIDE - 2.0, (N, 3)).astype(np.float32)
    dia = np.full((N,), 2.6, np.float32)
    beh = lambda: [GrowDivide(rate=0.35, threshold_diameter=3.2)]
    pol = G.RebuildPolicy(mode="every_k", k=4, displacement_bound=1.0)
    small = _forces_cfg(SIDE, pol, capacity=N)

    ladder = engine.CapacityLadder(small, beh())
    st = ladder.run(ladder.init_state(jnp.asarray(pos), diameter=dia), 10)
    assert ladder.config.capacity > N, "population never outgrew the seed"

    big = dataclasses.replace(small, capacity=ladder.config.capacity)
    sim = engine.Simulation(big, beh())
    st2 = sim.run(sim.init_state(jnp.asarray(pos), diameter=dia), 10)

    a1, a2 = np.asarray(st.pool.alive), np.asarray(st2.pool.alive)
    assert int(a1.sum()) == int(a2.sum())
    p1 = np.asarray(st.pool.position)[a1]
    p2 = np.asarray(st2.pool.position)[a2]
    o1, o2 = np.lexsort(p1.T), np.lexsort(p2.T)
    assert np.array_equal(p1[o1], p2[o2]), "ladder rewind broke bit-exactness"


# ---------------------------------------------------------------------------
# distributed every_k: ghost/migration-clean slabs skip with exact parity
# ---------------------------------------------------------------------------

_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import json
    import numpy as np
    import jax.numpy as jnp
    from repro.core import distributed, engine, grid

    SIDE, R = 64.0, 3.0
    # per slab: an inert 3x3x3 lattice (spacing 2.4 > dia + band) plus one
    # overlapping agent -> local relaxation, no cross-slab traffic
    lat = np.stack(np.meshgrid(*[np.arange(3) * 2.4 - 2.4] * 3),
                   -1).reshape(-1, 3)
    pos = []
    for cx in (8.0, 24.0, 40.0, 56.0):
        c = np.array([cx, SIDE / 2, SIDE / 2])
        pos.append(c + lat)
        pos.append((c + np.array([1.0, 0.55, 0.3]))[None])
    pos = np.concatenate(pos).astype(np.float32)
    n = pos.shape[0]
    dia = np.full((n,), 2.2, np.float32)
    ids = np.arange(n, dtype=np.int32)
    # fixed mid-gap boundaries: halo bands stay empty -> skips must occur
    # (the quantile boundaries would glue to each cluster's edge instead)
    fixed_b = jnp.asarray([0.0, 16.0, 32.0, 48.0, 64.0], jnp.float32)

    base = dict(capacity=512, domain_lo=(0., 0., 0.),
                domain_hi=(SIDE, SIDE, SIDE), interaction_radius=R,
                use_forces=True, max_per_box=32)
    mk = lambda cfg: distributed.DistConfig(
        engine=cfg, n_shards=4, local_capacity=128, halo_capacity=32,
        migrate_capacity=32)
    cfg_a = engine.EngineConfig(**base)
    cfg_b = engine.EngineConfig(**base, rebuild=grid.RebuildPolicy(
        mode="every_k", k=4, displacement_bound=1.0))

    out, counts = {}, {}
    for name, cfg in (("every_step", cfg_a), ("every_k", cfg_b)):
        sim = distributed.DistributedSimulation(mk(cfg))
        st = sim.init_state(jnp.asarray(pos), jnp.asarray(dia),
                            jnp.asarray(ids))
        st = dataclasses.replace(st, boundaries=fixed_b)
        rebuilds = skips = 0
        for _ in range(24):
            st = sim.step(st)
            per_shard = np.asarray(st.stats["rebuilds"])
            rebuilds += int(np.sum(per_shard))
            skips += per_shard.size - int(np.sum(per_shard))
        ch = sim.gather_channels(st)
        a = ch["alive"]
        out[name] = ch["position"][a][np.argsort(ch["agent_type"][a])]
        counts[name] = {"n": int(a.sum()), "rebuilds": rebuilds,
                        "skips": skips}

    d = float(np.abs(out["every_step"] - out["every_k"]).max())
    print("RESULT " + json.dumps({"max_d": d, **{
        f"{k}_{f}": v[f] for k, v in counts.items()
        for f in ("n", "rebuilds", "skips")}}))
""")


def test_distributed_every_k_skip_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["every_step_n"] == res["every_k_n"]
    assert res["every_step_skips"] == 0
    assert res["every_step_rebuilds"] == 4 * 24
    assert res["every_k_skips"] > 0, res
    assert res["every_k_rebuilds"] + res["every_k_skips"] == 4 * 24, res
    # isolated slabs, deterministic dynamics: parity is exact
    assert res["max_d"] < 1e-5, res
