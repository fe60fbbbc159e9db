"""Distributed ABM engine — the paper's §8 'future work' (multi-node), realized.

This module contains **no force/query/behavior logic of its own**: each slab
runs the SAME Algorithm-1 iteration body as the single-device engine
(engine.make_iteration_core — resident grid build, run-streaming or Pallas
forces, behaviors, effects merge, death compaction + birth commit, statics
bookkeeping, diffusion). The wrapper's job is purely distribution
(DESIGN.md §7):

  * **1-D slab domain decomposition** along x over mesh axis ``data``: each
    device owns agents with x ∈ [b_i, b_{i+1}). Slab boundaries come from
    population *quantiles* — the paper's §4.2 balancing (equal agents per NUMA
    domain) lifted to devices — and are re-derived every
    ``rebalance_frequency`` steps *inside* the jitted program.
  * **Ring halo exchange**: interaction radius r ≤ slab width ⇒ every cross-
    shard interaction partner lives in the adjacent slab; one
    ``ppermute`` left + one right per step ships the boundary band as *ghost*
    rows appended to the local pool. The ghost buffer layout is derived from
    the pool's channel spec (agents.pool_from_channels) — every channel,
    including behavior-owned extras like infection timers, crosses the
    boundary; ghosts are gather sources only (engine core ``owned`` mask).
    With ``detect_static`` the band widens to 2·r so box-granular disturbance
    (statics.py) stays a conservative superset across shard lines.
  * **Ring migration**: agents whose post-step x leaves the slab ship to the
    adjacent shard with the same channel packing and are appended through the
    §3.2 *birth-commit* path (compaction.commit_births) — newborn agents of
    this very step migrate like any other, preserving born_iter and all
    behavior state. Fixed-capacity buffers with overflow flags (never silent
    loss; stats.StepStats).
  * **Sharded diffusion**: the substance grid is split into x-slabs; each
    FTCS substep exchanges one-voxel face halos alongside the agent ghosts
    (_ShardedDiffusionOps / diffusion.step_slab). Agent coupling (secretion
    scatter, gradient/value sampling) routes through psum_scatter/all_gather
    so quantile agent slabs need not align with the fixed voxel slabs.

Everything runs under one ``shard_map`` program: the whole distributed step is
a single XLA executable per device.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import compaction, diffusion as diff_mod, grid as grid_mod
from .agents import AgentPool, make_pool, pool_from_channels
from .behaviors import Behavior
from .engine import (CapacityExhausted, EngineConfig, LadderConfig,
                     LadderDriverBase, colmap_rung, next_rung,
                     make_iteration_core, stage_pool)
from .stats import StepStats

OWNED = "owned"          # bool extra channel: local agent (True) vs ghost


class SlabCapacityError(ValueError):
    """An initial slab population exceeds local_capacity (init-time §4.2
    never-silent check). Typed so the distributed capacity ladder can catch
    exactly this condition and grow, rather than matching error prose."""


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Static distributed-run configuration.

    local_capacity:      slots per shard (live agents per slab must fit)
    halo_capacity:       ghost rows shipped per face per step
    migrate_capacity:    migrating agents shipped per face per step
    rebalance_frequency: re-derive quantile slab boundaries every this many
                         steps inside the jitted program (0 = keep the
                         boundaries fixed after init)
    """
    engine: EngineConfig
    n_shards: int
    local_capacity: int
    halo_capacity: int = 1024
    migrate_capacity: int = 256
    rebalance_frequency: int = 0

    @property
    def halo_width(self) -> float:
        """Ghost band thickness: r, or 2·r under detect_static (statics.py);
        plus the rebuild policy's cell slack so the band stays a conservative
        superset when every_k widens the grid cells (grid.RebuildPolicy), and
        plus the pair-list skin so a list built at radius r + skin still sees
        every cross-shard candidate (grid.PairListConfig)."""
        skin = (self.engine.pairlist.skin
                if self.engine.pairlist is not None else 0.0)
        return self.engine.interaction_radius * (
            2.0 if self.engine.detect_static else 1.0
        ) + self.engine.rebuild.cell_slack + skin

    @property
    def total_capacity(self) -> int:
        """Local pool width inside the step: owned slots + two ghost bands."""
        return self.local_capacity + 2 * self.halo_capacity


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistState:
    """Sharded simulation state. ``channels`` hold every pool channel as a
    global (n_shards·local_capacity, ...) array sharded on dim 0; shard i's
    agents live in slice [i·C, i·C + n_i)."""
    channels: Dict[str, jnp.ndarray]
    conc: jnp.ndarray               # diffusion slabs, sharded on x (dummy if unused)
    rng: jax.Array                  # (n_shards, 2) per-shard key
    boundaries: jnp.ndarray         # (n_shards + 1,) slab edges (replicated)
    iteration: jnp.ndarray          # () int32
    stats: StepStats                # per-shard (n_shards,) counters
    env: Optional[grid_mod.RebuildState] = None
                                    # per-shard cached grid build (RebuildPolicy
                                    # every_k): every leaf carries a leading
                                    # (n_shards,) axis; None under every_step


def quantile_boundaries(x: jnp.ndarray, alive: jnp.ndarray, n_shards: int,
                        lo: float, hi: float) -> jnp.ndarray:
    """Equal-population slab boundaries (paper §4.2 balancing).

    Robust to degenerate populations: with no live agents the inner
    boundaries collapse to ``hi`` (all-empty slabs are valid), and a heavily
    skewed distribution (single cluster) yields clamped, non-decreasing
    boundaries — possibly empty slabs, never an inverted or out-of-domain
    one.
    """
    big = jnp.where(alive, x, jnp.inf)
    xs = jnp.sort(big)
    n = jnp.sum(alive.astype(jnp.int32))
    qs = (jnp.arange(1, n_shards) * n) // n_shards
    inner = xs[jnp.clip(qs, 0, x.shape[0] - 1)]
    inner = jnp.clip(inner, lo, hi)            # n == 0 → inf → hi
    if n_shards > 1:
        inner = jax.lax.cummax(inner)          # monotone under skew/ties
    return jnp.concatenate([jnp.asarray([lo], inner.dtype), inner,
                            jnp.asarray([hi], inner.dtype)])


def partition_global(pool_channels: Dict[str, jnp.ndarray],
                     boundaries: jnp.ndarray, dcfg: DistConfig
                     ) -> Dict[str, jnp.ndarray]:
    """Host-side: scatter agents into per-shard slots [shard, local_capacity].

    Returns channels with leading dim n_shards*local_capacity, agents of shard
    i in slice [i*C, i*C + n_i). (Used at init; in-loop rebalancing moves
    agents through the migration path instead.) Agents beyond a slab's
    local_capacity are dropped — size capacity for the post-balance maximum.
    """
    x = pool_channels["position"][:, 0]
    alive = pool_channels["alive"]
    shard = jnp.clip(jnp.searchsorted(boundaries[1:-1], x, side="right"),
                     0, dcfg.n_shards - 1)
    out = {}
    c = dcfg.local_capacity
    # rank within shard via stable sort by (shard, index); dead rows sort
    # (and stay) at key n_shards so live rows need NOT form a prefix —
    # checkpoint restore re-partitions global buffers with dead gaps
    order = jnp.argsort(jnp.where(alive, shard, dcfg.n_shards),
                        stable=True)
    sorted_shard = jnp.where(alive[order], shard[order], dcfg.n_shards)
    first = jnp.searchsorted(sorted_shard, jnp.arange(dcfg.n_shards))
    rank_in_shard = jnp.arange(x.shape[0]) - first[jnp.clip(sorted_shard, 0,
                                                            dcfg.n_shards - 1)]
    dst = sorted_shard * c + rank_in_shard
    ok = alive[order] & (rank_in_shard < c)
    dst = jnp.where(ok, dst, dcfg.n_shards * c)          # parked → dropped
    for k, v in pool_channels.items():
        buf = jnp.zeros((dcfg.n_shards * c,) + v.shape[1:], v.dtype)
        out[k] = buf.at[dst].set(v[order], mode="drop")
    # alive additionally masks the unpacked tail of every slab
    out["alive"] = jnp.zeros((dcfg.n_shards * c,), bool).at[dst].set(
        ok, mode="drop")
    return out


def pack_channels(mask: jnp.ndarray, channels: Dict[str, jnp.ndarray],
                  cap: int) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Pack masked agents into fixed (cap, ...) buffers, one per channel.

    The buffer layout IS the pool's channel spec — whatever channels the pool
    carries (behavior extras included) are shipped, dtype-preserving. The
    packed ``alive`` doubles as the lane-validity mask (mask ⊆ alive; invalid
    lanes are zeroed). Returns (buffers, overflow_count).
    """
    idx, n = compaction.active_index_list(mask)
    take = idx[:cap]
    lane_ok = jnp.arange(cap) < jnp.minimum(n, cap)
    buf = {}
    for k, v in channels.items():
        g = v[take]
        keep = lane_ok.reshape((cap,) + (1,) * (g.ndim - 1))
        buf[k] = jnp.where(keep, g, jnp.zeros_like(g))
    buf["alive"] = lane_ok & channels["alive"][take]
    return buf, jnp.maximum(n - cap, 0)


def _ppermute_tree(tree, axis: str, perm):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.ppermute(a, axis, perm), tree)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class _ShardedDiffusionOps(diff_mod.DiffusionOps):
    """diffusion.DiffusionOps over x-slabs of the substance grid.

    ``step`` is the genuinely sharded compute: each substep exchanges
    one-voxel face halos with the ring neighbors (Neumann edges at the global
    faces) and runs the same FTCS arithmetic as the single-device grid
    (diffusion.step_slab — bit-identical per voxel). Agent coupling crosses
    slab lines through collectives, because quantile *agent* slabs need not
    align with the fixed *voxel* slabs: secretion scatters into a global-dims
    buffer reduced back to slabs with psum_scatter; sampling gathers the full
    grid (only traced when a behavior actually samples).
    """

    def __init__(self, spec: diff_mod.DiffusionSpec, origin, axis: str,
                 n_shards: int, fwd, bwd):
        super().__init__(spec, origin)
        self.axis, self.n_shards, self.fwd, self.bwd = axis, n_shards, fwd, bwd

    def step(self, conc, dt):
        recv_l = jax.lax.ppermute(conc[-1], self.axis, self.fwd)
        recv_r = jax.lax.ppermute(conc[0], self.axis, self.bwd)
        i = jax.lax.axis_index(self.axis)
        x_lo = jnp.where(i == 0, conc[0], recv_l)              # Neumann edge
        x_hi = jnp.where(i == self.n_shards - 1, conc[-1], recv_r)
        return diff_mod.step_slab(self.spec, conc, dt, x_lo, x_hi)

    def _gathered(self, conc):
        return jax.lax.all_gather(conc, self.axis, tiled=True)

    def sample(self, conc, position):
        return diff_mod.sample(self.spec, self._gathered(conc), position,
                               self.origin)

    def gradient(self, conc, position):
        return diff_mod.gradient(self.spec, self._gathered(conc), position,
                                 self.origin)

    def add_sources(self, conc, position, amount):
        g = jnp.zeros(self.spec.dims, jnp.float32)
        g = diff_mod.add_sources(self.spec, g, position, amount, self.origin)
        return conc + jax.lax.psum_scatter(g, self.axis,
                                           scatter_dimension=0, tiled=True)


def _channel_template(dcfg: DistConfig, behaviors: Sequence[Behavior]
                      ) -> AgentPool:
    """Zero pool defining the channel spec (ghost layout, state layout)."""
    specs: Dict[str, tuple] = {}
    for b in behaviors:
        specs.update(b.extra_specs())
    specs[OWNED] = ((), jnp.bool_, False)
    return make_pool(dcfg.total_capacity, extra_specs=specs,
                     policy=dcfg.engine.dtypes)


def make_distributed_step(dcfg: DistConfig, mesh, behaviors: Sequence[Behavior]
                          = (), axis: str = "data"):
    """Build the jitted shard_map step: DistState → DistState.

    Per shard and per step: halo exchange (spec-derived ghost rows appended
    to the local pool with owned=False) → the SHARED iteration core →
    optional in-loop quantile rebalance → ring migration through the
    birth-commit path → repack to local_capacity.
    """
    cfg = dcfg.engine
    n_shards = dcfg.n_shards
    c_local = dcfg.local_capacity
    hcap, mcap = dcfg.halo_capacity, dcfg.migrate_capacity
    if not 0 < hcap <= c_local or not 0 < mcap <= c_local:
        raise ValueError("halo/migrate capacity must be in (0, local_capacity]")
    if cfg.diffusion is not None and cfg.diffusion.dims[0] % n_shards:
        raise ValueError(f"diffusion dims[0]={cfg.diffusion.dims[0]} must be "
                         f"divisible by n_shards={n_shards} (x-slab sharding)")
    x_lo_dom = float(cfg.domain_lo[0])
    x_hi_dom = float(cfg.domain_hi[0])
    fwd = [(i, i + 1) for i in range(n_shards - 1)]
    bwd = [(i + 1, i) for i in range(n_shards - 1)]

    diff_ops = None
    if cfg.diffusion is not None:
        diff_ops = _ShardedDiffusionOps(cfg.diffusion,
                                        jnp.asarray(cfg.domain_lo, jnp.float32),
                                        axis, n_shards, fwd, bwd)
    core = make_iteration_core(cfg, behaviors, owned_channel=OWNED,
                               pvary_axes=(axis,), diff_ops=diff_ops)
    template = _channel_template(dcfg, behaviors)
    names = list(template.channels().keys())
    use_cache = cfg.rebuild.mode == "every_k"

    def step_shard(channels: Dict[str, jnp.ndarray], conc: jnp.ndarray,
                   rng: jax.Array, boundaries: jnp.ndarray,
                   iteration: jnp.ndarray,
                   env: Optional[grid_mod.RebuildState]):
        i = jax.lax.axis_index(axis)
        my_lo = boundaries[i]
        my_hi = boundaries[i + 1]
        alive = channels["alive"]
        x = channels["position"][:, 0]
        hw = jnp.float32(dcfg.halo_width)

        # ---- halo exchange: boundary bands → ghost rows of the neighbors ----
        band_l, ovf_hl = pack_channels(alive & (x < my_lo + hw), channels, hcap)
        band_r, ovf_hr = pack_channels(alive & (x > my_hi - hw), channels, hcap)
        ghosts_l = _ppermute_tree(band_r, axis, fwd)     # from shard i-1
        ghosts_r = _ppermute_tree(band_l, axis, bwd)     # from shard i+1
        # edge shards pack bands the ring never ships (no neighbor beyond the
        # domain face) — a pile-up against the wall must not flag overflow
        ovf_hl = jnp.where(i > 0, ovf_hl, 0)
        ovf_hr = jnp.where(i < n_shards - 1, ovf_hr, 0)
        # ring halo exactness also needs every *interior* slab to be at least
        # one band wide: a thinner one (quantile collapse against a pile-up —
        # even an empty slab) puts its two neighbors within r of each other
        # but two ring hops apart, so their pairs would be missed. The first/
        # last slabs may be arbitrarily thin (no shard beyond them). Flagged
        # on the same never-silent channel as the buffer overflows.
        thin = ((my_hi - my_lo < hw) & (i > 0)
                & (i < n_shards - 1)).astype(jnp.int32)

        full = {k: jnp.concatenate([channels[k], ghosts_l[k], ghosts_r[k]], 0)
                for k in names}
        full["extra." + OWNED] = jnp.concatenate(
            [jnp.ones((c_local,), bool), jnp.zeros((2 * hcap,), bool)], 0)
        pool = pool_from_channels(full)

        # ---- the shared Algorithm-1 iteration (engine.make_iteration_core) --
        n_ghosts = jnp.zeros((), jnp.int32)
        if use_cache:
            # a cached slab build is only valid over the layout it was built
            # on — which had every ghost slot dead (a build that saw live
            # ghosts marks itself dirty below, because next step's band holds
            # different agents). Live ghosts arriving NOW therefore force a
            # rebuild: the stale tables think their slots are empty.
            n_ghosts = (jnp.sum(ghosts_l["alive"].astype(jnp.int32))
                        + jnp.sum(ghosts_r["alive"].astype(jnp.int32)))
            env = dataclasses.replace(env, dirty=env.dirty | (n_ghosts > 0))
        pool, conc, rng, stats, env = core(pool, conc, rng, iteration, env)
        ch = pool.channels()
        owned = ch["extra." + OWNED].astype(bool)
        alive2 = ch["alive"] & owned
        x2 = ch["position"][:, 0]

        # ---- in-loop quantile rebalance (paper §4.2 balancing) ----
        if dcfg.rebalance_frequency > 0:
            def rebal(_):
                xg = jax.lax.all_gather(x2, axis, tiled=True)
                ag = jax.lax.all_gather(alive2, axis, tiled=True)
                return quantile_boundaries(xg, ag, n_shards, x_lo_dom,
                                           x_hi_dom)
            boundaries = jax.lax.cond(
                (iteration + 1) % dcfg.rebalance_frequency == 0,
                rebal, lambda b: b, boundaries)
            my_lo = boundaries[i]
            my_hi = boundaries[i + 1]

        # ---- ring migration: leavers append via the §3.2 birth-commit path --
        go_l = alive2 & (x2 < my_lo) & (i > 0)
        go_r = alive2 & (x2 >= my_hi) & (i < n_shards - 1)
        mig_l, ovf_ml = pack_channels(go_l, ch, mcap)
        mig_r, ovf_mr = pack_channels(go_r, ch, mcap)
        arrivals_l = _ppermute_tree(mig_r, axis, fwd)
        arrivals_r = _ppermute_tree(mig_l, axis, bwd)

        ch["alive"] = alive2 & ~go_l & ~go_r       # drop ghosts + leavers
        pool = compaction.compact(pool_from_channels(ch))
        ovf_in = jnp.zeros((), jnp.int32)
        n_arrive = jnp.zeros((), jnp.int32)
        for arr in (arrivals_l, arrivals_r):
            valid = arr["alive"]
            ovf_in += compaction.birth_overflow(pool, valid)
            n_arrive += jnp.sum(valid.astype(jnp.int32))
            # commit_births preserves every shipped channel (born_iter, owned,
            # behavior extras) — agents born this step migrate intact
            pool = compaction.commit_births(pool, arr, valid, iteration)

        if use_cache:
            # distribution events that reorder the slab on top of the core's
            # own deaths/births: live ghosts this step (their slots churn),
            # leavers (the end-of-step compact permutes), and arrivals
            # (append through slots the tables call dead). Any of them → the
            # cached tables no longer describe the next step's layout.
            n_leave = jnp.sum((go_l | go_r).astype(jnp.int32))
            env = dataclasses.replace(
                env, dirty=(env.dirty | (n_ghosts > 0) | (n_leave > 0)
                            | (n_arrive > 0)))

        n_final = pool.n_live
        ovf_cap = jnp.maximum(n_final - c_local, 0)     # clipped on repack
        out_ch = {k: v[:c_local] for k, v in pool.channels().items()}
        # an owned agent still outside its slab after this step's one ring
        # hop (displaced ≥2 slabs by a rebalance) begins the next iteration
        # with an incomplete neighborhood — nothing is dropped (it converges
        # one hop per step), so it gets its own never-silent counter rather
        # than polluting migrate_overflow's raise-the-buffer remediation
        xf = out_ch["position"][:, 0]
        in_flight = jnp.sum((out_ch["alive"]
                             & (((xf < my_lo) & (i > 0))
                                | ((xf >= my_hi) & (i < n_shards - 1)))
                             ).astype(jnp.int32))
        # which-capacity provenance (§4.3): each flag names exactly one
        # growable knob — halo_overflow → halo_capacity, migrate_overflow →
        # migrate_capacity, birth_overflow (staged newborns + arrivals +
        # repack clipping) → local_capacity with capacity_demand its rung
        # target; thin_slab is NOT growable (quantile geometry, not a buffer)
        stats = dataclasses.replace(
            stats,
            n_live=jnp.sum(out_ch["alive"].astype(jnp.int32)),
            halo_overflow=(ovf_hl + ovf_hr).astype(jnp.int32),
            migrate_overflow=(ovf_ml + ovf_mr).astype(jnp.int32),
            birth_overflow=(stats.birth_overflow + ovf_in
                            + ovf_cap).astype(jnp.int32),
            capacity_demand=(n_final + ovf_in
                             + stats.birth_overflow).astype(jnp.int32),
            thin_slab=thin.astype(jnp.int32),
            in_flight=in_flight.astype(jnp.int32))
        stats = jax.tree_util.tree_map(lambda v: v.reshape(1), stats)
        return out_ch, conc, rng.reshape(1, -1), boundaries, stats, env

    ch_specs = {k: P(axis) for k in names}
    # the env cache shards like the pool: every RebuildState leaf gains a
    # leading (n_shards,) axis (None under every_step — an empty pytree, so
    # the spec position is None too)
    env_specs = None
    if use_cache:
        env_specs = jax.tree_util.tree_map(
            lambda _: P(axis),
            grid_mod.initial_rebuild_state(
                cfg.grid_spec, dcfg.total_capacity,
                jnp.asarray(cfg.domain_lo, jnp.float32),
                jnp.asarray(cfg.cell_size, jnp.float32),
                pairlist=cfg.pairlist))
    in_specs = (ch_specs, P(axis), P(axis), P(), P(), env_specs)
    out_specs = (ch_specs, P(axis), P(axis), P(),
                 StepStats(**{f: P(axis) for f in StepStats.FIELDS}),
                 env_specs)

    def _shard_body(channels, conc, rng, boundaries, iteration, env):
        # per-shard env leaves arrive with a leading axis of 1; the core works
        # on unsharded shapes, so squeeze in and restore on the way out
        env_in = (None if env is None
                  else jax.tree_util.tree_map(lambda a: a[0], env))
        out_ch, conc2, rng2, boundaries2, stats, env_out = step_shard(
            channels, conc, rng.reshape(-1), boundaries, iteration, env_in)
        if env_out is not None:
            env_out = jax.tree_util.tree_map(lambda a: a[None], env_out)
        return out_ch, conc2, rng2, boundaries2, stats, env_out

    sharded = _shard_map(_shard_body, mesh, in_specs, out_specs)

    def step(state: DistState) -> DistState:
        ch, conc, rng, boundaries, stats, env = sharded(
            state.channels, state.conc, state.rng, state.boundaries,
            state.iteration, state.env)
        return DistState(channels=ch, conc=conc, rng=rng,
                         boundaries=boundaries,
                         iteration=state.iteration + 1, stats=stats, env=env)

    return jax.jit(step)


class DistributedSimulation:
    """Drop-in distributed counterpart of engine.Simulation.

    Same config + behaviors; state is sharded over ``dcfg.n_shards`` devices
    of ``mesh`` (default: the first n_shards of jax.devices()). Because every
    slab runs the shared iteration core, any scenario that runs on
    `Simulation` runs here unchanged — forces, behaviors, births/deaths,
    statics, and diffusion included.
    """

    def __init__(self, dcfg: DistConfig, behaviors: Sequence[Behavior] = (),
                 mesh=None, axis: str = "data"):
        self.dcfg = dcfg
        self.behaviors = list(behaviors)
        self.axis = axis
        if mesh is None:
            devices = jax.devices()
            if len(devices) < dcfg.n_shards:
                raise ValueError(
                    f"n_shards={dcfg.n_shards} needs {dcfg.n_shards} devices;"
                    f" found {len(devices)} {devices[0].platform} "
                    f"device(s)")
            mesh = jax.sharding.Mesh(np.array(devices[:dcfg.n_shards]),
                                     (axis,))
        self.mesh = mesh
        self._step_fn = make_distributed_step(dcfg, mesh, self.behaviors,
                                              axis)

    # -- state construction -------------------------------------------------
    def init_state(self, position, diameter=None, agent_type=None,
                   extra_init: Dict[str, jnp.ndarray] | None = None,
                   seed: int = 0) -> DistState:
        dcfg, cfg = self.dcfg, self.dcfg.engine
        position = jnp.asarray(position)
        staging = stage_pool(position.shape[0], self.behaviors, position,
                             diameter, agent_type, extra_init,
                             extra_specs={OWNED: ((), jnp.bool_, True)},
                             policy=cfg.dtypes)
        ch = staging.channels()
        boundaries = quantile_boundaries(ch["position"][:, 0], ch["alive"],
                                         dcfg.n_shards,
                                         float(cfg.domain_lo[0]),
                                         float(cfg.domain_hi[0]))
        # never-silent contract at init too: partition_global drops agents
        # past a slab's local_capacity, so refuse instead (host-side check —
        # heavy ties can pile a whole cluster into one quantile slab)
        b = np.asarray(boundaries)
        shard = np.clip(np.searchsorted(b[1:-1], np.asarray(ch["position"][:, 0]),
                                        side="right"), 0, dcfg.n_shards - 1)
        per_shard = np.bincount(shard[np.asarray(ch["alive"])],
                                minlength=dcfg.n_shards)
        if per_shard.max(initial=0) > dcfg.local_capacity:
            raise SlabCapacityError(
                f"slab populations {per_shard.tolist()} exceed "
                f"local_capacity={dcfg.local_capacity}; raise it (heavy ties "
                f"in x can defeat quantile balancing)")
        channels = partition_global(ch, boundaries, dcfg)
        dspec = cfg.diffusion
        conc = (jnp.zeros(dspec.dims, jnp.float32) if dspec
                else jnp.zeros((dcfg.n_shards, 1, 1)))
        rng = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(seed),
                                                    s))(
            jnp.arange(dcfg.n_shards, dtype=jnp.uint32))
        env = None
        if cfg.rebuild.mode == "every_k":
            # one empty-dirty cache per shard, stacked on a leading axis
            env0 = grid_mod.initial_rebuild_state(
                cfg.grid_spec, dcfg.total_capacity,
                jnp.asarray(cfg.domain_lo, jnp.float32),
                jnp.asarray(cfg.cell_size, jnp.float32),
                pairlist=cfg.pairlist)
            env = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (dcfg.n_shards,)
                                           + a.shape).copy(), env0)
        return DistState(channels=channels, conc=conc, rng=rng,
                         boundaries=boundaries,
                         iteration=jnp.zeros((), jnp.int32),
                         stats=StepStats.zeros((dcfg.n_shards,)), env=env)

    # -- public API ----------------------------------------------------------
    def step(self, state: DistState) -> DistState:
        return self._step_fn(state)

    def run(self, state: DistState, n_iterations: int,
            check_overflow: bool = False) -> DistState:
        """Run ``n_iterations``; with ``check_overflow`` the host enforces the
        §4.2 never-silent-loss contract over every per-shard flag."""
        for i in range(n_iterations):
            state = self._step_fn(state)
            if check_overflow:
                flags = state.stats.flags()   # only nonzero §4.2 flags
                if flags:
                    s = state.stats
                    remediation = {
                        "halo_overflow": (
                            f"halo overflow (ghost band exceeded "
                            f"halo_capacity={self.dcfg.halo_capacity}); "
                            f"raise halo_capacity"),
                        "thin_slab": (
                            f"an interior slab is thinner than the "
                            f"{self.dcfg.halo_width:.3g} ghost band (one-hop "
                            f"ring cannot ship every cross-shard pair); "
                            f"revisit boundaries / fewer shards"),
                        "migrate_overflow": (
                            f"migration overflow (ring buffer "
                            f"migrate_capacity={self.dcfg.migrate_capacity} "
                            f"exceeded)"),
                        "in_flight": (
                            f"{flags.get('in_flight', 0)} agents in flight "
                            f"across >1 slab (a rebalance moved a boundary "
                            f"further than one slab width; their next step "
                            f"sees an incomplete neighborhood) — lower "
                            f"rebalance_frequency or accept the transient by "
                            f"polling stats.in_flight instead of "
                            f"check_overflow"),
                        "box_overflow": (
                            "grid run overflow on a shard; raise "
                            "EngineConfig.max_per_run / max_per_box"),
                        "colmap_overflow": (
                            f"K1 column-map overflow on a shard (per-shard "
                            f"demand {np.asarray(s.colmap_demand).tolist()} "
                            f"column blocks > the map's width "
                            f"{self.dcfg.engine.colmap_capacity}); raise "
                            f"EngineConfig.colmap_width"),
                        "pair_overflow": (
                            f"pair-list overflow on a shard (an agent has "
                            f"more in-range(+skin) candidates than "
                            f"max_pairs; per-shard demand "
                            f"{np.asarray(s.pair_demand).tolist()}); raise "
                            f"PairListConfig.max_pairs"),
                        "birth_overflow": (
                            f"local pool overflow on a shard (staged "
                            f"newborns / migration arrivals / repack "
                            f"exceeded local_capacity="
                            f"{self.dcfg.local_capacity}; per-shard demand "
                            f"{np.asarray(s.capacity_demand).tolist()}); "
                            f"raise DistConfig.local_capacity"),
                    }
                    # report in severity order, not dict order
                    for f in ("halo_overflow", "thin_slab",
                              "migrate_overflow", "in_flight",
                              "box_overflow", "colmap_overflow",
                              "pair_overflow", "birth_overflow"):
                        if f in flags:
                            raise RuntimeError(
                                f"iteration {i}: {remediation[f]}")
        return state

    def gather_channels(self, state: DistState) -> Dict[str, np.ndarray]:
        """Host-side: fetch the global channel arrays (live agents only are
        meaningful; order is arbitrary across shards)."""
        return {k: np.asarray(v) for k, v in state.channels.items()}


# ---------------------------------------------------------------------------
# Distributed capacity ladder (DESIGN.md §4.3) — agreed global rungs
# ---------------------------------------------------------------------------

class DistributedCapacityLadder(LadderDriverBase):
    """`DistributedSimulation.run` with automatic growth, one global rung.

    Every capacity knob (local pool slots, halo band, migration ring,
    max_per_run) is *static and shared* across shards — a single shard's
    overflow therefore grows the knob for the whole mesh ("agreed global
    rung"): rung targets are the max of the per-shard demand provenance in
    StepStats, so one recompile serves every slab and the shard_map program
    stays homogeneous. Like the single-device CapacityLadder, the
    overflowing iteration is re-run from its pre-step state, which keeps
    trajectories bit-identical to a pre-sized run.

    Non-buffer exactness flags (thin_slab, in_flight) are not growable —
    they raise with remediation guidance instead of looping forever.
    """

    def __init__(self, dcfg: DistConfig, behaviors: Sequence[Behavior] = (),
                 ladder=None, mesh=None, axis: str = "data"):
        self.ladder = ladder or LadderConfig()
        self.dcfg = dcfg
        self.behaviors = list(behaviors)
        self.axis = axis
        self._mesh = mesh
        self.rungs: list = []
        self.recompiles = 0
        self._sim = DistributedSimulation(dcfg, self.behaviors, mesh, axis)

    @property
    def sim(self) -> DistributedSimulation:
        return self._sim

    def init_state(self, *args, **kwargs) -> DistState:
        """init with ladder semantics: an initial population too big for a
        slab grows local_capacity instead of raising (bounded retries)."""
        for _ in range(self.ladder.max_grows_per_step):
            try:
                return self._sim.init_state(*args, **kwargs)
            except SlabCapacityError:
                d = self.dcfg
                new_local = next_rung(d.local_capacity, d.local_capacity + 1,
                                      self.ladder.growth_factor,
                                      self.ladder.round_to)
                self._rebuild(dataclasses.replace(d, local_capacity=new_local),
                              iteration=-1)
        raise RuntimeError("init_state: local_capacity growth did not "
                           "converge (pathological initial distribution)")

    # -- growth policy -------------------------------------------------------
    def _diagnose(self, stats: StepStats) -> Optional[DistConfig]:
        d, lad = self.dcfg, self.ladder
        tot = lambda f: int(np.asarray(jnp.sum(stats[f])))
        if tot("thin_slab"):
            raise RuntimeError(
                "thin interior slab (quantile geometry, not a buffer size) — "
                "the ladder cannot grow past it; use fewer shards or a wider "
                "domain")
        if tot("in_flight"):
            raise RuntimeError(
                "agents in flight across >1 slab after a rebalance — lower "
                "rebalance_frequency (not a capacity problem)")
        changes = {}
        eng = d.engine
        if tot("box_overflow"):
            demand = int(np.asarray(jnp.max(stats["box_demand"])))
            if eng.environment == "hash_grid":
                need = -(-demand // grid_mod.HASH_K_MULT)
                eng = dataclasses.replace(eng, max_per_box=next_rung(
                    eng.max_per_box, need, lad.growth_factor))
            else:
                cur = eng.grid_spec.run_capacity
                eng = dataclasses.replace(eng, max_per_run=next_rung(
                    cur, demand, lad.growth_factor))
        if tot("colmap_overflow"):
            eng = dataclasses.replace(eng, colmap_width=colmap_rung(
                eng, int(np.asarray(jnp.max(stats["colmap_demand"]))),
                lad.growth_factor))
        if tot("pair_overflow"):
            # agreed global rung: one max_pairs for every shard, sized off
            # the worst per-shard demand
            demand = int(np.asarray(jnp.max(stats["pair_demand"])))
            eng = dataclasses.replace(eng, pairlist=dataclasses.replace(
                eng.pairlist, max_pairs=next_rung(
                    eng.pairlist.max_pairs, demand, lad.growth_factor)))
        if eng is not d.engine:
            changes["engine"] = eng
        if tot("halo_overflow"):
            demand = d.halo_capacity + int(np.asarray(
                jnp.max(stats["halo_overflow"])))
            changes["halo_capacity"] = next_rung(
                d.halo_capacity, demand, lad.growth_factor, lad.round_to)
        if tot("migrate_overflow"):
            demand = d.migrate_capacity + int(np.asarray(
                jnp.max(stats["migrate_overflow"])))
            changes["migrate_capacity"] = next_rung(
                d.migrate_capacity, demand, lad.growth_factor, lad.round_to)
        if tot("birth_overflow"):
            demand = int(np.asarray(jnp.max(stats["capacity_demand"])))
            new_local = next_rung(d.local_capacity, demand,
                                  lad.growth_factor, lad.round_to)
            if (lad.max_capacity is not None
                    and new_local * d.n_shards > lad.max_capacity):
                raise CapacityExhausted(
                    f"capacity ladder exhausted: per-shard demand {demand} "
                    f"needs {new_local}×{d.n_shards} slots > "
                    f"max_capacity={lad.max_capacity}",
                    demand=demand, rung=new_local * d.n_shards,
                    max_capacity=lad.max_capacity)
            changes["local_capacity"] = new_local
        if not changes:
            return None
        new_d = dataclasses.replace(d, **changes)
        # static contract: halo/migrate buffers never exceed local_capacity
        if new_d.local_capacity < max(new_d.halo_capacity,
                                      new_d.migrate_capacity):
            new_d = dataclasses.replace(
                new_d, local_capacity=max(new_d.halo_capacity,
                                          new_d.migrate_capacity))
        return new_d

    def _rebuild(self, new_d: DistConfig, iteration: int) -> None:
        self._log_rungs(
            iteration,
            [(f, getattr(self.dcfg, f), getattr(new_d, f))
             for f in ("local_capacity", "halo_capacity", "migrate_capacity")]
            + [(f, getattr(self.dcfg.engine, f), getattr(new_d.engine, f))
               for f in ("max_per_box", "max_per_run")]
            + [("colmap_width", self.dcfg.engine.colmap_capacity,
                new_d.engine.colmap_capacity)]
            + ([("max_pairs", self.dcfg.engine.pairlist.max_pairs,
                 new_d.engine.pairlist.max_pairs)]
               if (new_d.engine.pairlist is not None
                   and self.dcfg.engine.pairlist is not None) else []))
        self.dcfg = new_d
        self._sim = DistributedSimulation(new_d, self.behaviors, self._mesh,
                                          self.axis)

    def _restage(self, state: DistState, old_local: int, new_local: int
                 ) -> DistState:
        """Host-side re-pack of every shard's slab into the new local width.

        Each shard's live prefix is preserved verbatim; new tail slots are
        zero (dead) — the distributed analog of compaction.grow_channels
        (compaction.repack_slabs, shared with checkpoint restore).
        """
        ch = compaction.repack_slabs(state.channels, self.dcfg.n_shards,
                                     old_local, new_local)
        return dataclasses.replace(state, channels=ch)

    def _grow(self, new_d: DistConfig, prev: DistState,
              iteration: int) -> DistState:
        old_local = self.dcfg.local_capacity
        old_total = self.dcfg.total_capacity
        old_pl = self.dcfg.engine.pairlist
        self._rebuild(new_d, iteration)
        if new_d.local_capacity != old_local:
            prev = self._restage(prev, old_local, new_d.local_capacity)
        new_pl = new_d.engine.pairlist
        if (prev.env is not None and prev.env.pairs is not None
                and new_pl is not None and old_pl is not None
                and (new_d.total_capacity != old_total
                     or new_pl.max_pairs != old_pl.max_pairs)):
            # (S, C, P) tables: grow_pairlist pads the trailing axes only —
            # an overflowed cached list never survives a kept step (the
            # rewind discards the overflowing step's output), so the zero
            # padding is exactly what a pre-sized build would hold
            prev = dataclasses.replace(
                prev, env=dataclasses.replace(
                    prev.env, pairs=grid_mod.grow_pairlist(
                        prev.env.pairs, new_d.total_capacity,
                        new_pl.max_pairs)))
        if prev.env is not None and new_d.total_capacity != old_total:
            # the cached grid spans the in-step pool (owned + ghost bands);
            # grow it alongside. grow_grid_state's dead-key/iota padding is
            # exactly what a pre-sized build over the wider pool would have
            # produced (live slots form a prefix whenever the cache is
            # clean), so the rewound trajectory stays bit-identical — no
            # dirty-forcing needed, which would instead reshuffle the skip
            # schedule
            prev = dataclasses.replace(
                prev, env=dataclasses.replace(
                    prev.env, grid=grid_mod.grow_grid_state(
                        prev.env.grid, new_d.total_capacity)))
        return prev
