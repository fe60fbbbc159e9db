"""Verlet pair-list cache (grid.PairList, DESIGN.md §3.4) — coverage + parity.

Contracts tested:

  * the skin-coverage property: no pair within ``r`` at *current* positions
    is ever absent from a list built at radius ``r + skin`` while per-agent
    euclidean displacement stays ≤ ``skin/2`` (uniform, clustered and
    anisotropic populations — hypothesis property test);
  * the build itself is exact: with generous capacities, each row's listed
    set equals the brute-force in-range(+skin) neighbor set;
  * per-kernel outputs are BIT-EXACT vs the fused streamed sweep when
    ``skin=0`` + every-step rebuilds (XLA and Pallas force paths);
  * under ``every_k`` skin reuse, a reused list serves a step identically
    to a fresh streamed build from the same pool state (the extra stale
    candidates contribute exact zeros);
  * ``max_pairs`` rung overflow → ladder rewind is bit-identical to a
    pre-sized run, with ``pair_overflow``/``pair_demand`` provenance in
    ``StepStats.flags()`` (single-device here; 4-shard in the subprocess
    test alongside streamed-vs-pairlist distributed parity).
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis_compat import given, settings, st

from repro.core import EngineConfig, Simulation, engine, grid
from repro.core.behaviors import Infection, INFECTED

SIDE = 48.0


def _cfg(n, **kw):
    base = dict(capacity=n, domain_lo=(0, 0, 0), domain_hi=(SIDE,) * 3,
                interaction_radius=3.0, max_per_box=32, query_chunk=256)
    base.update(kw)
    return EngineConfig(**base)


def _sir_state(sim, n, pos):
    types = np.zeros(n, np.int32)
    types[: n // 20] = INFECTED
    return sim.init_state(pos, diameter=np.full(n, 2.5, np.float32),
                          agent_type=types,
                          extra_init={"infect_timer":
                                      np.full(n, 8, np.int32)})


def _uniform(n, rng):
    return rng.uniform(2, SIDE - 2, (n, 3)).astype(np.float32)


def _clustered(n, rng):
    centers = rng.uniform(8, SIDE - 8, (4, 3))
    which = rng.integers(0, 4, n)
    p = centers[which] + rng.normal(0, 2.0, (n, 3))
    return np.clip(p, 1.0, SIDE - 1.0).astype(np.float32)


def _anisotropic(n, rng):
    p = rng.uniform(2, SIDE - 2, (n, 3))
    p[:, 2] = rng.uniform(20, 28, n)            # thin slab in z
    return p.astype(np.float32)


_DOMAINS = {"uniform": _uniform, "clustered": _clustered,
            "anisotropic": _anisotropic}


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_pairlist_config_validation():
    with pytest.raises(ValueError):
        grid.PairListConfig(skin=-0.1)
    with pytest.raises(ValueError):
        grid.PairListConfig(max_pairs=0)
    # skin > 0 without every_k reuse is pointless and rejected loudly
    with pytest.raises(ValueError):
        _cfg(64, pairlist=grid.PairListConfig(skin=0.5, max_pairs=8))
    # the pair list serves the fused sweep only
    with pytest.raises(ValueError):
        _cfg(64, fused_sweep=False,
             pairlist=grid.PairListConfig(skin=0.0, max_pairs=8))
    with pytest.raises(ValueError):
        _cfg(64, detect_static=True,
             pairlist=grid.PairListConfig(skin=0.0, max_pairs=8))
    # cell width covers the pair-list filter radius
    cfg = _cfg(64, rebuild=grid.RebuildPolicy(mode="every_k", k=4,
                                              displacement_bound=0.2),
               pairlist=grid.PairListConfig(skin=0.9, max_pairs=8))
    assert cfg.cell_size == pytest.approx(3.0 + 0.9)


def test_grow_pairlist_padding():
    p = grid.initial_pairlist(4, 3)
    p = dataclasses.replace(
        p, idx=jnp.arange(12, dtype=jnp.int32).reshape(4, 3),
        count=jnp.array([3, 1, 0, 2], jnp.int32))
    g = grid.grow_pairlist(p, 6, 5)
    assert g.idx.shape == (6, 5) and g.run_off.shape == (6, 10)
    assert np.array_equal(np.asarray(g.idx[:4, :3]),
                          np.arange(12).reshape(4, 3))
    assert np.asarray(g.idx)[:, 3:].max() == 0 and np.asarray(g.idx)[4:].max() == 0
    assert np.array_equal(np.asarray(g.count), [3, 1, 0, 2, 0, 0])
    with pytest.raises(ValueError):
        grid.grow_pairlist(p, 2, 5)


# ---------------------------------------------------------------------------
# build exactness + the skin-coverage property
# ---------------------------------------------------------------------------

def _build_list(pos, r, skin, max_pairs=192):
    """Resident build + pair list at radius r+skin; returns (sorted positions,
    alive mask, PairList)."""
    n = pos.shape[0]
    cfg = _cfg(n, interaction_radius=r,
               rebuild=grid.RebuildPolicy(mode="every_k", k=8,
                                          displacement_bound=0.25),
               pairlist=grid.PairListConfig(skin=skin, max_pairs=max_pairs))
    spec = cfg.grid_spec
    pool = engine.stage_pool(n, [], pos)
    res = engine.build_env(cfg, spec, pool,
                           jnp.asarray(cfg.domain_lo, jnp.float32),
                           jnp.asarray(cfg.cell_size, jnp.float32))
    pairs = grid.build_pairlist(spec, res.grid, res.pool.position,
                                res.pool.alive, radius=r + skin,
                                max_pairs=max_pairs)
    return (np.asarray(res.pool.position), np.asarray(res.pool.alive), pairs)


def _listed_sets(pairs):
    idx = np.asarray(pairs.idx)
    stored = np.asarray(pairs.run_off)[:, -1]
    return [set(idx[i, :stored[i]].tolist()) for i in range(idx.shape[0])]


def test_build_matches_bruteforce_inrange_sets():
    rng = np.random.default_rng(0)
    pos = _uniform(500, rng)
    r, skin = 3.0, 0.8
    spos, alive, pairs = _build_list(pos, r, skin)
    listed = _listed_sets(pairs)
    live = np.where(alive)[0]
    d2 = np.sum((spos[live, None] - spos[None, live]) ** 2, -1)
    rad2 = (r + skin) ** 2
    for a, i in enumerate(live):
        want = {int(live[b]) for b in np.where(d2[a] <= rad2)[0] if live[b] != i}
        assert listed[i] == want, f"row {i}"
    assert int(np.asarray(pairs.demand)) == max(len(s) for s in listed)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.2, 1.2),
       st.sampled_from(("uniform", "clustered", "anisotropic")))
def test_skin_coverage_property(seed, skin, domain):
    """No current-position pair within r is missing from a list built at
    r + skin, as long as per-agent euclidean displacement ≤ skin/2."""
    rng = np.random.default_rng(seed)
    n, r = 300, 3.0
    pos0 = _DOMAINS[domain](n, rng)
    spos, alive, pairs = _build_list(pos0, r, skin)
    listed = _listed_sets(pairs)
    # displace every agent by at most skin/2 (euclidean)
    step = rng.normal(size=(n, 3))
    step *= (rng.uniform(0, skin / 2, (n, 1))
             / np.maximum(np.linalg.norm(step, axis=1, keepdims=True), 1e-9))
    pos1 = spos + step.astype(np.float32)
    live = np.where(alive)[0]
    d2 = np.sum((pos1[live, None] - pos1[None, live]) ** 2, -1)
    for a, i in enumerate(live):
        for b in np.where(d2[a] <= r * r)[0]:
            j = int(live[b])
            if j == i:
                continue
            assert j in listed[i], (
                f"pair ({i},{j}) within r after bounded motion but unlisted "
                f"(skin={skin}, domain={domain})")


# ---------------------------------------------------------------------------
# skin=0 + every-step rebuilds: bit-exact vs the streamed sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pairlist_bit_exact_vs_streamed(impl):
    """Cross-mode equality holds bit-for-bit here because every pruned
    candidate contributes an exact +0.0 per lane.  The one caveat (see
    DESIGN.md §3.4): XLA:CPU's lane-axis reduction is lane-position
    sensitive, so a near-cancelling row can differ by ~1 ulp when packing
    shifts the nonzero lanes.  This seed/geometry has no such row — the
    assertions below are exact and deterministic; geometry with the
    cancellation is exercised tolerance-checked in the 4-shard test."""
    n, rng = 1000, np.random.default_rng(1)
    pos = _uniform(n, rng)
    states = {}
    for pl in (None, grid.PairListConfig(skin=0.0, max_pairs=96)):
        sim = Simulation(_cfg(n, force_impl=impl, pairlist=pl),
                         [Infection(radius=3.0, beta=0.4, recovery_time=8)])
        states[pl is None] = sim.run(_sir_state(sim, n, pos), 6,
                                     check_overflow=True)
    a, b = states[True], states[False]
    for ch in ("position", "agent_type", "force_nnz"):
        assert np.array_equal(np.asarray(getattr(a.pool, ch)),
                              np.asarray(getattr(b.pool, ch))), ch
    assert np.array_equal(np.asarray(a.pool.extra["infect_timer"]),
                          np.asarray(b.pool.extra["infect_timer"]))


# ---------------------------------------------------------------------------
# every_k skin reuse: a reused list serves the step exactly
# ---------------------------------------------------------------------------

def test_skin_reuse_step_matches_fresh_streamed():
    """After several reuse steps, one further step served by the cached list
    equals a step served by a fresh every-step streamed build from the SAME
    pool state — the stale extra candidates are exact zeros (compare
    order-invariantly: the two configs sort the pool differently)."""
    n, rng = 900, np.random.default_rng(2)
    pos = _uniform(n, rng)
    rb = grid.RebuildPolicy(mode="every_k", k=8, displacement_bound=0.45)
    d = Simulation(_cfg(n, rebuild=rb,
                        pairlist=grid.PairListConfig(skin=0.9, max_pairs=128)),
                   [Infection(radius=3.0, beta=0.4, recovery_time=8)])
    st = _sir_state(d, n, pos)
    skips = 0
    for _ in range(6):
        st = d.step(st)
        skips += 1 - int(st.stats.rebuilds)
    assert skips > 0, "skin budget should allow at least one reuse step"
    e = Simulation(_cfg(n), [Infection(radius=3.0, beta=0.4, recovery_time=8)])
    st_e = engine.EngineState(pool=st.pool, conc=st.conc, rng=st.rng,
                              iteration=st.iteration, stats=st.stats,
                              env=None)
    n1, n2 = d.step(st), e.step(st_e)

    def canon(p):
        P = np.asarray(p.position)[np.asarray(p.alive)]
        return P[np.lexsort(P.T)]

    assert np.array_equal(canon(n1.pool), canon(n2.pool))
    assert np.array_equal(np.sort(np.asarray(n1.pool.force_nnz)),
                          np.sort(np.asarray(n2.pool.force_nnz)))


# ---------------------------------------------------------------------------
# max_pairs ladder rung: overflow provenance + bit-identical rewind
# ---------------------------------------------------------------------------

def test_pair_overflow_provenance_and_raise():
    n, rng = 600, np.random.default_rng(3)
    pos = _clustered(n, rng)
    sim = Simulation(_cfg(n, pairlist=grid.PairListConfig(skin=0.0,
                                                          max_pairs=1)),
                     [Infection(radius=3.0, beta=0.4, recovery_time=8)])
    st = sim.step(_sir_state(sim, n, pos))
    flags = st.stats.flags()
    assert "pair_overflow" in flags
    assert int(st.stats.pair_demand) > 1
    with pytest.raises(RuntimeError, match="max_pairs"):
        sim.run(_sir_state(sim, n, pos), 1, check_overflow=True)


def test_max_pairs_rung_rewind_bit_parity():
    n, rng = 900, np.random.default_rng(4)
    pos = _uniform(n, rng)
    beh = lambda: [Infection(radius=3.0, beta=0.4, recovery_time=8)]
    lad = engine.CapacityLadder(
        _cfg(n, pairlist=grid.PairListConfig(skin=0.0, max_pairs=2)), beh())
    st = _sir_state(lad, n, pos)
    for _ in range(4):
        st = lad.step(st)
    assert any(r["field"] == "max_pairs" for r in lad.rungs), lad.rungs
    grown = lad.config.pairlist.max_pairs
    pre = Simulation(_cfg(n, pairlist=grid.PairListConfig(skin=0.0,
                                                          max_pairs=grown)),
                     beh())
    sp = pre.run(_sir_state(pre, n, pos), 4, check_overflow=True)
    for ch in ("position", "agent_type", "force_nnz"):
        assert np.array_equal(np.asarray(getattr(st.pool, ch)),
                              np.asarray(getattr(sp.pool, ch))), ch


def test_max_pairs_rung_with_cached_env_bit_parity():
    """The rewind under every_k: growing a cached (overflowed) list via
    grow_pairlist zero-padding must reproduce what a pre-sized run holds —
    the overflowing step's output is discarded, so a capped table never
    survives into a kept step."""
    n, rng = 900, np.random.default_rng(5)
    pos = _uniform(n, rng)
    rb = grid.RebuildPolicy(mode="every_k", k=8, displacement_bound=0.45)
    beh = lambda: [Infection(radius=3.0, beta=0.4, recovery_time=8)]
    lad = engine.CapacityLadder(
        _cfg(n, rebuild=rb,
             pairlist=grid.PairListConfig(skin=0.9, max_pairs=2)), beh())
    st = _sir_state(lad, n, pos)
    for _ in range(6):
        st = lad.step(st)
    assert any(r["field"] == "max_pairs" for r in lad.rungs), lad.rungs
    grown = lad.config.pairlist.max_pairs
    pre = Simulation(
        _cfg(n, rebuild=rb,
             pairlist=grid.PairListConfig(skin=0.9, max_pairs=grown)), beh())
    sp = _sir_state(pre, n, pos)
    for _ in range(6):
        sp = pre.step(sp)
    for ch in ("position", "agent_type", "force_nnz"):
        assert np.array_equal(np.asarray(getattr(st.pool, ch)),
                              np.asarray(getattr(sp.pool, ch))), ch


# ---------------------------------------------------------------------------
# distributed: 4-shard parity + distributed max_pairs rung (subprocess)
# ---------------------------------------------------------------------------

_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax.numpy as jnp
    from repro.core import distributed, engine, grid
    from repro.core.behaviors import Infection, INFECTED, RandomWalk

    SIDE, n = 48.0, 1024
    rng = np.random.default_rng(7)
    pos = rng.uniform(2, SIDE - 2, (n, 3)).astype(np.float32)
    dia = np.full(n, 2.5, np.float32)
    types = np.zeros(n, np.int32)
    types[:32] = INFECTED

    def cfg(pairlist=None):
        return engine.EngineConfig(
            capacity=n, domain_lo=(0., 0., 0.), domain_hi=(SIDE,) * 3,
            interaction_radius=3.0, max_per_box=32, query_chunk=256,
            pairlist=pairlist)

    def beh():
        # RandomWalk drives agents across slab boundaries -> mid-run
        # migration exercises the dirty-on-structural-change conditions
        return [RandomWalk(sigma=0.35),
                Infection(radius=3.0, beta=0.4, recovery_time=8)]

    def dist(c):
        return distributed.DistConfig(engine=c, n_shards=4,
                                      local_capacity=2 * n // 4,
                                      halo_capacity=256, migrate_capacity=256)

    def init(sim):
        return sim.init_state(jnp.asarray(pos), jnp.asarray(dia),
                              jnp.asarray(types),
                              extra_init={"infect_timer":
                                          np.full(n, 8, np.int32)})

    def canon(ch):
        a = ch["alive"]
        o = np.lexsort(ch["position"][a].T)
        return ch["position"][a][o], ch["agent_type"][a][o]

    # (a) streamed vs pairlist(skin=0): parity through 8 steps with
    #     migration underway (ints exact, floats up to reduce-order ulps)
    out, migrated = {}, 0
    for pl in (None, grid.PairListConfig(skin=0.0, max_pairs=96)):
        sim = distributed.DistributedSimulation(dist(cfg(pl)), beh())
        st = init(sim)
        for _ in range(8):
            st = sim.step(st)
        out[pl is None] = canon(sim.gather_channels(st))
    dp = float(np.abs(out[True][0] - out[False][0]).max())
    dt = int(np.abs(out[True][1].astype(np.int64)
                    - out[False][1].astype(np.int64)).max())

    # (b) distributed max_pairs rung: ladder from a too-small table vs a
    #     pre-sized run — bit-identical after the rewind
    lad = distributed.DistributedCapacityLadder(
        dist(cfg(grid.PairListConfig(skin=0.0, max_pairs=2))), beh())
    st = init(lad)
    for _ in range(4):
        st = lad.step(st)
    grown = lad.dcfg.engine.pairlist.max_pairs
    rung_hit = any(r["field"] == "max_pairs" for r in lad.rungs)
    pre = distributed.DistributedSimulation(
        dist(cfg(grid.PairListConfig(skin=0.0, max_pairs=grown))), beh())
    sp = init(pre)
    for _ in range(4):
        sp = pre.step(sp)
    la, pa = canon(lad.sim.gather_channels(st)), canon(pre.gather_channels(sp))
    ladder_dp = float(np.abs(la[0] - pa[0]).max())

    print("RESULT " + json.dumps({
        "n_true": int(out[True][0].shape[0]),
        "n_false": int(out[False][0].shape[0]),
        "max_dpos": dp, "max_dtype": dt,
        "rung_hit": rung_hit, "grown": int(grown),
        "ladder_dpos": ladder_dp,
        "ladder_n": [int(la[0].shape[0]), int(pa[0].shape[0])]}))
""")


def test_pairlist_4shard_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["n_true"] == res["n_false"]
    # Cross-mode float channels are exact up to reduction-order ulps: the
    # pruned candidates contribute exact +0.0 per lane, but XLA:CPU's
    # lane-axis sum is lane-POSITION sensitive (verified: bit-equal per-lane
    # addends summed at packed vs streamed lane offsets differ by 1-2 ulp in
    # near-cancelling rows), so an occasional last-bit wiggle survives.
    # Integer channels and same-mode comparisons stay bit-exact.
    assert res["max_dpos"] <= 1e-5, res
    assert res["max_dtype"] == 0, res
    assert res["rung_hit"], res
    assert res["ladder_n"][0] == res["ladder_n"][1]
    assert res["ladder_dpos"] == 0.0, res
