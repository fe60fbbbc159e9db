"""Simulation engine — paper §2 Algorithm 1, one fused XLA program per iteration.

Iteration structure (paper L2–L19):
  pre-standalone ops:   resident grid rebuild (§3.1 + §4.2: ONE permutation
                        grid-orders the pool, sorts agents in memory, and
                        compacts the dead — the periodic Morton sort is a
                        no-op special case of it), diffusion step, static-flag
                        update (§5, box-granular, from last iteration's
                        bookkeeping)
  agent ops:            mechanical forces over the *active tiles* only
                        (§5 skipping at tile granularity, windowed sweep),
                        displacement integration, behaviors
  post-standalone ops:  death compaction + birth commit (§3.2), statistics

The paper's two thread barriers (L6/L15) vanish: under jit the whole iteration
is a single XLA program — the strongest possible form of 'maximize the parallel
part' (Amdahl, paper Challenge 1).

**The iteration core is engine-agnostic** (:func:`make_iteration_core`,
DESIGN.md §7): the same body serves the single-device `Simulation` and each
slab of the distributed shard_map engine. The distributed wrapper
parameterizes it with an *owned* channel (local agents vs ghost force-sources
from neighboring slabs), the mesh axes its collectives vary over
(``pvary_axes``), and a sharded `DiffusionOps` — nothing about forces,
behaviors, births/deaths, statics, or diffusion is duplicated per engine.

Environment selection mirrors the paper's environment interface: the optimized
uniform grid (default), the scatter-table 'standard' grid, or brute force
(Fig 11 comparison).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import compaction, diffusion as diff_mod, forces as force_mod, grid as grid_mod
from . import health as health_mod, morton, statics as statics_mod
from . import telemetry
from .agents import AgentPool, DtypePolicy, make_pool
from .behaviors import Behavior, BehaviorEffects
from .stats import StepStats


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration (part of the jit closure)."""
    capacity: int
    domain_lo: Tuple[float, float, float]
    domain_hi: Tuple[float, float, float]
    interaction_radius: float
    dt: float = 1.0
    use_forces: bool = True
    fused_sweep: bool = True               # evaluate forces + every declared
                                           # behavior kernel against ONE
                                           # pruned candidate stream per
                                           # tile (grid.resident_apply_fused;
                                           # uniform_grid only — other
                                           # environments run the sequential
                                           # per-phase sweeps). False keeps
                                           # the sequential path (parity
                                           # tests, breakdown benchmark).
    detect_static: bool = False            # paper detect_static_agents
    sort_frequency: int = 0                # paper Fig 12 (0 = never sort).
                                           # Resident environments
                                           # (uniform_grid/brute_force) sort
                                           # every step as part of the grid
                                           # build; this only drives the
                                           # Morton sort of scatter/hash envs.
    environment: str = "uniform_grid"      # uniform_grid | scatter_grid | hash_grid | brute_force
    force_impl: str = "xla"                # xla | pallas (K1 windowed kernel;
                                           # interpret mode off-TPU, native on TPU)
    max_per_box: int = 16
    max_per_run: Optional[int] = None      # gather width per 3-box z-run (None → 3·K)
    colmap_width: Optional[int] = None     # K1's column-map width: column
                                           # blocks a 128-row block may
                                           # visit (None → derived from the
                                           # run capacity; colmap_capacity)
    query_chunk: int = 2048
    adhesion: Optional[Tuple[Tuple[float, ...], ...]] = None  # type adhesion matrix
    force: force_mod.ForceParams = dataclasses.field(default_factory=force_mod.ForceParams)
    diffusion: Optional[diff_mod.DiffusionSpec] = None
    diffusion_substeps: int = 1
    rebuild: grid_mod.RebuildPolicy = dataclasses.field(
        default_factory=grid_mod.RebuildPolicy)
                                           # when the grid build runs
                                           # (every_step | every_k with a
                                           # displacement bound; grid.py)
    pairlist: Optional[grid_mod.PairListConfig] = None
                                           # Verlet pair-list stage: at each
                                           # rebuild, compact the in-range(+
                                           # skin) candidates into a fixed
                                           # (C, max_pairs) table and serve
                                           # the fused sweep from it
                                           # (grid.build_pairlist; None keeps
                                           # the streamed 9-run sweep)
    sort_impl: str = "auto"                # key-sort realization of the grid
                                           # build (grid.SORT_IMPLS): O(N)
                                           # counting sort on host/xla,
                                           # argsort as the parity oracle
    dtypes: DtypePolicy = dataclasses.field(default_factory=DtypePolicy)
                                           # channel storage dtypes (§4.3:
                                           # narrower aux channels → more
                                           # agents per byte per rung)
    health: Optional[health_mod.HealthConfig] = dataclasses.field(
        default_factory=health_mod.HealthConfig)
                                           # in-graph health watchdog folded
                                           # into StepStats.health (§7.5);
                                           # None disables it entirely

    def __post_init__(self):
        if self.sort_impl not in grid_mod.SORT_IMPLS:
            raise ValueError(f"sort_impl must be one of {grid_mod.SORT_IMPLS},"
                             f" got {self.sort_impl!r}")
        if self.rebuild.mode == "every_k":
            if self.environment != "uniform_grid":
                raise ValueError(
                    f"rebuild.mode='every_k' requires "
                    f"environment='uniform_grid' (the cached resident tables "
                    f"are what a skipped step reuses), got "
                    f"environment={self.environment!r}")
            if self.detect_static:
                raise ValueError(
                    "rebuild.mode='every_k' is incompatible with "
                    "detect_static=True: box-granular disturbance "
                    "aggregation (statics.py) reads fresh per-step tables; "
                    "set rebuild=RebuildPolicy() or detect_static=False")
        if self.pairlist is not None:
            if self.environment != "uniform_grid" or not self.fused_sweep:
                raise ValueError(
                    "pairlist requires environment='uniform_grid' and "
                    "fused_sweep=True (the pair table prunes the fused "
                    "resident candidate stream; other environments / the "
                    "sequential sweeps never consume it)")
            if self.detect_static:
                raise ValueError(
                    "pairlist is incompatible with detect_static=True: the "
                    "pair table is built over all live rows while static "
                    "detection re-masks queries per step from fresh tables; "
                    "disable one of the two")
            if self.pairlist.skin > 0 and self.rebuild.mode != "every_k":
                raise ValueError(
                    "pairlist.skin > 0 only pays off under "
                    "rebuild.mode='every_k' (the skin exists to let cached "
                    "lists survive between rebuilds); use skin=0 with "
                    "every-step rebuilds")

    @property
    def cell_size(self) -> float:
        """Grid box edge: the interaction radius, widened by the larger of
        the rebuild policy's displacement bound (stale-table stencils must
        cover every in-radius pair — grid.RebuildPolicy coverage argument)
        and the pair-list skin (a fresh build's 3×3×3 stencil must reach
        every candidate within r + skin for grid.build_pairlist)."""
        skin = self.pairlist.skin if self.pairlist is not None else 0.0
        return self.interaction_radius + max(self.rebuild.cell_slack, skin)

    @property
    def colmap_capacity(self) -> int:
        """K1's column-map width: ``colmap_width``, or by default one
        derived from the grid's run capacity
        (kernels/ops.default_colmap_width)."""
        if self.colmap_width is not None:
            return self.colmap_width
        from ..kernels import ops as kops
        return kops.default_colmap_width(self.grid_spec.run_capacity)

    @property
    def grid_spec(self) -> grid_mod.GridSpec:
        dims = tuple(max(1, int(math.ceil((hi - lo) / self.cell_size)))
                     for lo, hi in zip(self.domain_lo, self.domain_hi))
        return grid_mod.GridSpec(dims=dims, max_per_box=self.max_per_box,
                                 max_per_run=self.max_per_run,
                                 query_chunk=self.query_chunk)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    pool: AgentPool
    conc: jnp.ndarray                    # diffusion grid ((1,1,1) dummy if unused)
    rng: jax.Array
    iteration: jnp.ndarray               # () int32
    stats: StepStats                     # per-iteration counters (stats.py)
    env: Optional[grid_mod.RebuildState] = None
                                         # cached grid build carried across
                                         # steps (RebuildPolicy every_k);
                                         # None under every_step


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScenarioParams:
    """Dynamic per-run scenario knobs, traced INTO the iteration core.

    ``EngineConfig`` is static — every float in it is baked into the jit
    program, so two runs with different dt or β are two compiles. This
    pytree carries the knobs that may differ per run *as traced values*:
    one program serves any parameter point, which is what lets the
    ensemble engine (ensemble.py) vmap hundreds of differently-
    parameterized simulations in lockstep and the simulation service
    (serve/sim_service.py) admit a new parameter point into a free lane
    without recompiling.

    dt:    () float32 — overrides ``cfg.dt`` (None → use the static value).
    force: ForceParams field overrides (e.g. ``{"k_rep": x}``) as traced
           scalars; empty → the static ``cfg.force``. Not supported with
           ``force_impl='pallas'`` (the kernel bakes its constants).
    rates: free-form behavior knobs, exposed to behaviors as
           ``ctx.params`` — a behavior opts in by taking a callable
           parameter (``Infection(beta=lambda ctx: ctx.params["beta"])``,
           behaviors.resolve).

    The dict *key sets* are static structure (part of the jit cache key);
    only the values are traced.
    """
    dt: Optional[jnp.ndarray] = None
    force: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    rates: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)

    @classmethod
    def of(cls, dt: Optional[float] = None,
           force: Optional[Dict[str, float]] = None,
           **rates) -> "ScenarioParams":
        """Scalar-array ScenarioParams from plain Python numbers."""
        return cls(
            dt=None if dt is None else jnp.asarray(dt, jnp.float32),
            force={k: jnp.asarray(v, jnp.float32)
                   for k, v in (force or {}).items()},
            rates={k: jnp.asarray(v) for k, v in rates.items()})


@dataclasses.dataclass
class StepContext:
    """What behaviors may read/use during one iteration."""
    config: EngineConfig
    dt: float
    domain_lo: jnp.ndarray
    domain_hi: jnp.ndarray
    iteration: jnp.ndarray
    owned: jnp.ndarray                       # (C,) bool — live agents this
                                             # engine instance owns; behaviors
                                             # must act on this mask, never on
                                             # pool.alive (under the distributed
                                             # engine, alive also covers ghost
                                             # force-sources whose effects are
                                             # the neighbor shard's to commit)
    neighbor_apply: Callable                 # (pair_fn, out_specs) -> dict
    substance_gradient: Callable             # positions -> (N, 3)
    substance_value: Callable                # positions -> (N,)
    neighbor_results: Dict[str, Dict[str, jnp.ndarray]] = dataclasses.field(
        default_factory=dict)                # fused-sweep outputs, keyed by
                                             # PairKernel.name (empty on the
                                             # sequential path — behaviors
                                             # fall back to neighbor_apply)
    params: Dict[str, jnp.ndarray] = dataclasses.field(
        default_factory=dict)                # ScenarioParams.rates — traced
                                             # per-run behavior knobs ({} when
                                             # the caller passed none)


# -- environment dispatch (module-level: shared by both engines) -------------

_ENV_METHOD = {  # EngineConfig.environment → grid.make_builder method
    "uniform_grid": "resident",
    "brute_force": "resident",   # resident build kept for statics bookkeeping
    "scatter_grid": "scatter",
    "hash_grid": "hash",
}


def build_env(cfg: EngineConfig, spec: grid_mod.GridSpec, pool: AgentPool,
              origin: jnp.ndarray, box_size: jnp.ndarray
              ) -> grid_mod.BuildResult:
    """Build the iteration's environment via the unified builder factory.

    Resident environments (uniform_grid, and brute_force — which keeps the
    grid for statics bookkeeping) come back with a *permuted pool*: the pool
    itself is the key-sorted layout. Scatter/hash leave the pool unchanged.
    The engine consumes the BuildResult overflow surface only for the
    environments whose queries it makes exact through the ladder
    (uniform/hash); the scatter baseline's per-box truncation is deliberate
    'standard implementation' behavior, surfaced in the result but not
    flagged in StepStats.
    """
    if cfg.environment not in _ENV_METHOD:
        raise ValueError(cfg.environment)
    builder = grid_mod.make_builder(spec, method=_ENV_METHOD[cfg.environment],
                                    sort_impl=cfg.sort_impl)
    return builder(pool, origin, box_size)


def make_neighbor_apply(cfg: EngineConfig, spec: grid_mod.GridSpec, grid_env,
                        channels: Dict[str, jnp.ndarray],
                        default_mask: jnp.ndarray,
                        pvary_axes: Tuple[str, ...] = ()):
    """One neighbor_apply closure per step.

    Every closure takes ``(pair_fn, out_specs, query_mask=None)`` — the mask
    defaults to ``default_mask`` (the live *owned* set; ghost rows of a
    distributed slab are gather sources, never queries). The uniform grid
    runs the resident tiled loop (grid.resident_apply): contiguous query
    tiles, each stencil column read as one window (per-row z-run gathers
    where a window does not fit), and whole-tile skipping driven by the
    mask (§5/O6 — this is where static tiles drop out of the trip count). The hash grid streams its 27 probes through
    grid.phased_chunk_apply; scatter ('standard implementation') and brute
    force keep the wide chunk_apply loop.
    """
    capacity = channels["position"].shape[0]

    if cfg.environment == "uniform_grid":
        def apply(pair_fn, out_specs, query_mask=None):
            if query_mask is None:
                query_mask = default_mask
            return grid_mod.resident_apply(spec, grid_env, channels,
                                           query_mask, pair_fn, out_specs,
                                           pvary_axes=pvary_axes)
        return apply

    if cfg.environment == "hash_grid":
        def phase_fn(q_pos, q_slot, j):
            ids, valid = grid_mod.hash_grid_probe(spec, grid_env, q_pos, j)
            valid &= ids != q_slot[:, None]              # exclude self
            return ids, valid

        def apply(pair_fn, out_specs, query_mask=None):
            if query_mask is None:
                query_mask = default_mask
            query_idx, n_query = compaction.active_index_list(query_mask)
            return grid_mod.phased_chunk_apply(
                channels, channels, query_idx, n_query, phase_fn, 27,
                pair_fn, out_specs, cfg.query_chunk, pvary_axes=pvary_axes)
        return apply

    if cfg.environment == "scatter_grid":
        def box_cand(qp):
            return grid_mod.scatter_grid_candidates(spec, grid_env, qp)
    elif cfg.environment == "brute_force":
        ids_all = jnp.arange(capacity, dtype=jnp.int32)

        def box_cand(qp):
            q = qp.shape[0]
            ids = jnp.broadcast_to(ids_all[None], (q, capacity))
            valid = jnp.broadcast_to(channels["alive"][None], (q, capacity))
            return ids, valid
    else:
        raise ValueError(f"unknown environment {cfg.environment}")

    def cand_fn(q_pos, q_slot):
        ids, valid = box_cand(q_pos)
        valid &= ids != q_slot[:, None]                  # exclude self
        return ids, valid

    def apply(pair_fn, out_specs, query_mask=None):
        if query_mask is None:
            query_mask = default_mask
        query_idx, n_query = compaction.active_index_list(query_mask)
        return grid_mod.chunk_apply(channels, channels, query_idx, n_query,
                                    cand_fn, pair_fn, out_specs,
                                    cfg.query_chunk, pvary_axes=pvary_axes)
    return apply


# -- fused-sweep introspection (CI examples-smoke; DESIGN.md §3.2) -----------

def registered_kernels(cfg: EngineConfig, behaviors: Sequence[Behavior]
                       ) -> List[grid_mod.PairKernel]:
    """The static PairKernel descriptors make_iteration_core registers
    (masks unresolved — they are per-step values)."""
    kernels: List[grid_mod.PairKernel] = []
    if cfg.use_forces:
        adhesion = (jnp.asarray(cfg.adhesion, jnp.float32)
                    if cfg.adhesion is not None else None)
        kernels.append(grid_mod.PairKernel(
            "force", force_mod.make_force_pair_fn(cfg.force, adhesion),
            force_mod.FORCE_OUT_SPECS, reads=force_mod.FORCE_READS))
    for b in behaviors:
        kernels.extend(b.neighbor_kernels())
    return kernels


def realized_footprint(cfg: EngineConfig, behaviors: Sequence[Behavior]
                       ) -> Tuple[str, ...]:
    """Union of channels the step's fused sweep will actually stream."""
    return grid_mod.fused_reads(registered_kernels(cfg, behaviors))


def check_kernel_footprints(cfg: EngineConfig, behaviors: Sequence[Behavior],
                            block: int = 4, width: int = 8
                            ) -> Tuple[str, ...]:
    """Trace every registered kernel against ONLY its declared footprint.

    In a real fused sweep an undeclared read can be masked by another
    kernel's declaration landing the channel in the gathered union; tracing
    each pair_fn in isolation (jax.eval_shape — no FLOPs) makes it a loud
    KeyError regardless. Also validates declared reads and outputs against
    the pool layout. Returns the realized footprint. CI's examples-smoke job
    runs this for every example (examples/check_footprints.py)."""
    pool = stage_pool(max(block, 1), behaviors,
                      jnp.zeros((1, 3), jnp.float32), policy=cfg.dtypes)
    channels = pool.channels()
    for k in registered_kernels(cfg, behaviors):
        missing = [ch for ch in k.reads if ch not in channels]
        if missing:
            raise KeyError(
                f"kernel {k.name!r} declares channels the pool does not "
                f"have: {missing} (pool has {sorted(channels)})")
        # the sweep always slices position for run_bounds, declared or not
        q_names = dict.fromkeys(("position",) + tuple(k.reads))
        q = {ch: jax.ShapeDtypeStruct((block,) + channels[ch].shape[1:],
                                      channels[ch].dtype) for ch in q_names}
        nbr = {ch: jax.ShapeDtypeStruct(
            (block, width) + channels[ch].shape[1:], channels[ch].dtype)
            for ch in k.reads}
        valid = jax.ShapeDtypeStruct((block, width), jnp.bool_)
        rows = jax.ShapeDtypeStruct((block,), jnp.int32)
        try:
            out = jax.eval_shape(k.pair_fn, q, nbr, valid, rows)
        except KeyError as e:
            raise KeyError(
                f"kernel {k.name!r} reads channel {e} it did not declare — "
                f"add it to PairKernel.reads (declared: {k.reads})") from None
        undeclared_out = sorted(set(out) - set(k.out_specs))
        if undeclared_out:
            raise KeyError(
                f"kernel {k.name!r} returns outputs {undeclared_out} "
                f"missing from its out_specs {sorted(k.out_specs)}")
    return realized_footprint(cfg, behaviors)


# -- the iteration core ------------------------------------------------------

# The step's phases, each a ``jax.named_scope`` over its ops. A compiled op
# belongs to the innermost of these names in its ``op_name`` path (a sweep a
# behavior calls through ctx.neighbor_apply is the sweep's), or to none.
# Scopes are metadata: they change no op of the compiled program.
PHASES = ("grid_build", "pairlist_build", "statics", "diffusion",
          grid_mod.SWEEP_SCOPE, "behaviors", "health", "commit")
# Scopes nested inside those phases, on the same rule: K1's column-map build
# and kernel (inside the sweep's; kernels/ops.py), and the commit's three
# parts.
SUBPHASES = ("colmap_build", "k1", "deaths", "compaction", "births")


def _phase(name: str):
    if name not in PHASES + SUBPHASES:
        raise ValueError(f"{name!r} is not one of {PHASES + SUBPHASES}")
    return jax.named_scope(name)


def make_iteration_core(cfg: EngineConfig, behaviors: Sequence[Behavior],
                        *, owned_channel: Optional[str] = None,
                        pvary_axes: Tuple[str, ...] = (),
                        diff_ops: Optional[diff_mod.DiffusionOps] = None):
    """Build the pure Algorithm-1 iteration body both engines share.

    Returns ``core(pool, conc, rng, iteration, env, params=None) -> (pool,
    conc, rng, StepStats, env)``: resident build (or cached-build reuse under
    RebuildPolicy every_k — ``env`` carries the grid.RebuildState, None
    under every_step) → run-streaming/Pallas forces → behaviors → effects
    merge → death compaction + birth commit → statics bookkeeping →
    diffusion step — exactly the paper's iteration, over whatever pool view
    the caller hands in.

    owned_channel: name of a bool extra channel distinguishing agents this
      pool view *owns* from ghost force-sources appended by a distributed
      wrapper (None → everything alive is owned, the single-device case).
      Ghosts contribute to neighbor reductions and statics disturbance but
      are never queried, never acted on by behaviors, never counted in stats,
      and never committed — their authoritative step happens on the shard
      that owns them. Newborns inherit owned=True (they are committed by the
      shard that staged them).
    pvary_axes: mesh axes the pool is sharded over (threaded to the query
      loops so their carries are marked varying under shard_map).
    diff_ops: substance-grid strategy (diffusion.DiffusionOps). Defaults to
      the full-grid single-device implementation; the distributed engine
      substitutes slab-sharded ops with face-halo exchange.

    The optional trailing ``params`` argument (a :class:`ScenarioParams`
    pytree of traced scalars) overrides dt / force constants / behavior
    rates at *runtime* — one compiled program serves every parameter point.
    ``params=None`` (both engines' default) keeps the static ``cfg`` values
    and is bit-identical to the pre-params core; the ensemble engine
    (ensemble.py) vmaps the core over a leading lane axis of params.
    """
    if cfg.force_impl == "pallas" and cfg.environment != "uniform_grid":
        raise ValueError("force_impl='pallas' requires the uniform_grid "
                         "environment (the kernel consumes its resident "
                         "grid tables)")
    behaviors = list(behaviors)
    # fused sweep registry (DESIGN.md §3.2): every behavior-declared pair
    # kernel joins the force kernel in ONE resident sweep per step; names key
    # the ctx.neighbor_results handoff, so they must be unique ("force" is
    # the engine's own kernel)
    behavior_kernels = []
    for b in behaviors:
        behavior_kernels.extend(b.neighbor_kernels())
    knames = [k.name for k in behavior_kernels]
    if len(set(knames)) != len(knames) or "force" in knames:
        raise ValueError(
            f"behavior neighbor_kernels() names must be unique and must not "
            f"shadow the engine's 'force' kernel, got {knames} — give each "
            f"behavior instance a distinct .name")
    fused = cfg.fused_sweep and cfg.environment == "uniform_grid"
    spec = cfg.grid_spec
    origin = jnp.asarray(cfg.domain_lo, jnp.float32)
    dlo = jnp.asarray(cfg.domain_lo, jnp.float32)
    dhi = jnp.asarray(cfg.domain_hi, jnp.float32)
    box_size = jnp.asarray(cfg.cell_size, jnp.float32)   # radius + rebuild slack
    adhesion = (jnp.asarray(cfg.adhesion, jnp.float32)
                if cfg.adhesion is not None else None)
    force_pair = force_mod.make_force_pair_fn(cfg.force, adhesion)
    if diff_ops is None and cfg.diffusion is not None:
        diff_ops = diff_mod.DiffusionOps(cfg.diffusion, origin)

    def owned_of(pool: AgentPool) -> jnp.ndarray:
        if owned_channel is None:
            return pool.alive
        return pool.extra[owned_channel].astype(bool) & pool.alive

    def sort_pool(pool: AgentPool) -> AgentPool:
        keys = morton.morton_keys(pool.position, origin, box_size, spec.dims)
        keys = jnp.where(pool.alive, keys, grid_mod._DEAD_KEY)
        order = jnp.argsort(keys).astype(jnp.int32)
        return compaction.apply_permutation(pool, order)

    use_cache = cfg.rebuild.mode == "every_k"
    pl = cfg.pairlist
    pair_radius = (cfg.interaction_radius + pl.skin) if pl is not None else 0.0

    def build_pairs(pool: AgentPool, grid_env) -> Optional[grid_mod.PairList]:
        if pl is None:
            return None
        with _phase("pairlist_build"):
            return grid_mod.build_pairlist(
                spec, grid_env, pool.position, pool.alive,
                radius=pair_radius, max_pairs=pl.max_pairs,
                chunk=cfg.query_chunk, pvary_axes=pvary_axes)

    def core(pool: AgentPool, conc: jnp.ndarray, rng: jax.Array,
             it: jnp.ndarray, env: Optional[grid_mod.RebuildState] = None,
             params: Optional[ScenarioParams] = None):
        rng, k_force, *bkeys = jax.random.split(rng, 2 + len(behaviors))
        stats = StepStats.zeros()

        # dynamic scenario knobs (ScenarioParams): traced dt / force
        # constants replace the static closure values; with params=None the
        # expressions below are the compile-time constants they always were
        dt = cfg.dt if params is None or params.dt is None else params.dt
        if params is not None and params.force:
            if cfg.force_impl == "pallas":
                raise ValueError(
                    "ScenarioParams.force overrides require force_impl='xla' "
                    "(the Pallas kernel bakes its force constants)")
            fp = dataclasses.replace(cfg.force, **params.force)
            fpair = force_mod.make_force_pair_fn(fp, adhesion)
        else:
            fp, fpair = cfg.force, force_pair
        rates = params.rates if params is not None else {}

        # ---------------- pre standalone ops ----------------
        # Resident envs reorder every build (the permutation IS the §4.2
        # sort); the periodic Morton sort only serves scatter/hash.
        with _phase("grid_build"):
            if cfg.sort_frequency > 0 and cfg.environment in ("scatter_grid",
                                                              "hash_grid"):
                pool = jax.lax.cond(it % cfg.sort_frequency == 0,
                                    sort_pool, lambda p: p, pool)
            rebuilt = jnp.ones((), jnp.int32)
            pairs = None
            if not use_cache:
                res = build_env(cfg, spec, pool, origin, box_size)
                pool, grid_env = res.pool, res.grid
                pairs = build_pairs(pool, grid_env)
            else:
                # every_k (uniform_grid only, enforced by EngineConfig): rebuild
                # when the cache is dirty (structural change last step), the k
                # budget is spent, or accumulated displacement exceeds the bound
                # the widened cells were sized for — otherwise skip the
                # permutation + table build outright and query the stale tables
                # (grid.RebuildPolicy coverage argument). A cached pair list has
                # its own, euclidean budget: it covers every in-range pair only
                # while 2·pair_disp ≤ skin (grid.PairListConfig).
                do_build = (env.dirty | (env.steps_since >= cfg.rebuild.k)
                            | (env.disp_accum > cfg.rebuild.displacement_bound))
                if pl is not None:
                    do_build = do_build | (2.0 * env.pair_disp > pl.skin)

                def _fresh(pool, env):
                    res = build_env(cfg, spec, pool, origin, box_size)
                    return res.pool, grid_mod.RebuildState(
                        grid=res.grid,
                        steps_since=jnp.zeros((), jnp.int32),
                        disp_accum=jnp.zeros((), jnp.float32),
                        dirty=jnp.zeros((), bool),
                        pairs=build_pairs(res.pool, res.grid),
                        pair_disp=(jnp.zeros((), jnp.float32)
                                   if pl is not None else None))

                pool, env = jax.lax.cond(do_build, _fresh,
                                         lambda pool, env: (pool, env), pool, env)
                grid_env = env.grid
                pairs = env.pairs
                rebuilt = do_build.astype(jnp.int32)
            box_overflow = stats.box_overflow
            box_demand = stats.box_demand
            if cfg.environment == "uniform_grid":
                # query exactness bound: every 3-box z-run must fit the run
                # gather capacity (DESIGN.md §4.2 overflow contract); the demand
                # is the which-capacity provenance the ladder sizes rungs from
                box_demand = grid_env.max_run_count.astype(jnp.int32)
                box_overflow = (grid_env.max_run_count
                                > spec.run_capacity).astype(jnp.int32)
            elif cfg.environment == "hash_grid":
                # same contract: a bucket fuller than the probe gather width
                # would silently truncate candidates (grid.hash_grid_probe)
                box_demand = grid_env.max_bucket_count.astype(jnp.int32)
                box_overflow = (
                    grid_env.max_bucket_count
                    > grid_mod.HASH_K_MULT * spec.max_per_box).astype(jnp.int32)
            pair_overflow = stats.pair_overflow
            pair_demand = stats.pair_demand
            if pairs is not None:
                # same never-silent contract as the run/bucket capacities: a row
                # demanding more than max_pairs entries truncated its list; the
                # demand is the which-capacity provenance the ladder sizes the
                # max_pairs rung from (§4.2/§4.3)
                pair_demand = pairs.demand
                pair_overflow = (pairs.demand > pl.max_pairs).astype(jnp.int32)

        with _phase("diffusion"):
            if cfg.diffusion is not None:
                sub_dt = dt / cfg.diffusion_substeps
                for _ in range(cfg.diffusion_substeps):
                    conc = diff_ops.step(conc, sub_dt)

        channels = {k: v for k, v in pool.channels().items()
                    if not k.startswith("extra.")}
        owned_alive = owned_of(pool)
        nbr_apply = make_neighbor_apply(cfg, spec, grid_env, channels,
                                        default_mask=owned_alive,
                                        pvary_axes=pvary_axes)

        # static flags from last iteration's bookkeeping (paper §5):
        # box-granular aggregation over the grid tables — no extra
        # neighbor sweep (statics.py). Ghost rows carry their owner's
        # bookkeeping, so boundary disturbance crosses shards.
        with _phase("statics"):
            if cfg.detect_static and cfg.environment in ("uniform_grid",
                                                         "brute_force"):
                static = statics_mod.update_static_flags(pool, spec, grid_env, it)
                pool = dataclasses.replace(pool, static=static)

        pos0 = pool.position
        dia0 = pool.diameter

        # ---------------- agent ops: fused neighbor sweep ----------------
        # Forces and every behavior-declared pair kernel evaluate against ONE
        # candidate stream per block, pruned to the union of their declared
        # channel footprints (grid.resident_apply_fused). Fusing is a pure
        # scheduling change: the sequential path's behavior sweeps read the
        # same pre-force channel snapshot (the nbr_apply closure captures
        # ``channels`` before integration), so per-kernel results are
        # bit-exact vs the per-phase sweeps (tests/test_fused.py).
        active = None
        if cfg.use_forces:
            if cfg.detect_static:
                active = owned_alive & ~pool.static
            else:
                active = owned_alive
        nbr_results: Dict[str, Dict[str, jnp.ndarray]] = {}
        work = {}                         # StepStats.WORK_FIELDS of what ran
        colmap = None                     # K1's ColmapWork, if K1 ran
        if fused:
            kernels = []
            if cfg.use_forces:
                kernels.append(grid_mod.PairKernel(
                    "force", fpair, force_mod.FORCE_OUT_SPECS,
                    reads=force_mod.FORCE_READS, query_mask=active))
            kernels.extend(behavior_kernels)
            if kernels:
                # extra.* channels join the gatherable set here — a kernel
                # that declares them streams them; nothing else does
                channels_full = pool.channels()
                if cfg.use_forces and cfg.force_impl == "pallas":
                    # K1 stays a single in-kernel pass for the force; the
                    # remaining kernels share one pruned XLA sweep over the
                    # same grid tables (kernels/ops.fused_resident_sweep)
                    from ..kernels import ops as kops
                    nbr_results, colmap = kops.fused_resident_sweep(
                        spec, grid_env, channels_full, kernels,
                        default_mask=owned_alive, origin=origin,
                        box_size=box_size, k_rep=cfg.force.k_rep,
                        adhesion=cfg.adhesion,
                        adhesion_band=cfg.force.adhesion_band,
                        chunk=cfg.query_chunk, pvary_axes=pvary_axes,
                        maxb=cfg.colmap_capacity, pairs=pairs)
                    xla_kernels = [k for k in kernels if k.name != "force"]
                else:
                    nbr_results = grid_mod.resident_apply_fused(
                        spec, grid_env, channels_full, kernels,
                        default_mask=owned_alive, chunk=cfg.query_chunk,
                        pvary_axes=pvary_axes, pairs=pairs)
                    xla_kernels = kernels
                # the XLA sweep's work, counted outside its block loop
                work = dict(zip(StepStats.SWEEP_FIELDS,
                                grid_mod.fused_sweep_work(
                                    spec, grid_env, xla_kernels,
                                    owned_alive, channels_full["position"],
                                    chunk=cfg.query_chunk, pairs=pairs)))
                # the later phases read the pool after the sweep: what they
                # write (the walk's positions, say) is then not live across
                # the build and the sweep, which sets the step's peak memory
                pool, nbr_results = jax.lax.optimization_barrier(
                    (pool, nbr_results))

        # ---------------- agent ops: forces ----------------
        force_arr = None                  # kept for the health guard below
        if cfg.use_forces:
            if "force" in nbr_results:
                res = nbr_results["force"]
            elif cfg.force_impl == "pallas":
                # K1 over the resident layout: the kernel consumes the
                # step's grid tables directly (no sort/unsort) and skips
                # fully-static row blocks (kernels/ops.py)
                from ..kernels import ops as kops
                f, nnz, colmap = kops.collision_force_resident(
                    pool.position, pool.diameter, pool.agent_type,
                    pool.alive, active, grid_env.starts, grid_env.counts,
                    origin, box_size,
                    dims=spec.dims, k_rep=cfg.force.k_rep,
                    adhesion=cfg.adhesion,
                    adhesion_band=cfg.force.adhesion_band,
                    maxb=cfg.colmap_capacity,
                    span=kops.colmap_span(spec.run_capacity))
                res = {"force": f, "force_nnz": nnz}
            else:
                res = nbr_apply(fpair, force_mod.FORCE_OUT_SPECS,
                                query_mask=active)
            force_arr = res["force"]
            dx = force_mod.displacement(res["force"], fp, dt)
            new_pos = jnp.clip(pool.position + dx, dlo, dhi)
            new_pos = jnp.where(active[:, None], new_pos, pool.position)
            force_nnz = jnp.where(active, res["force_nnz"],
                                  pool.force_nnz).astype(pool.force_nnz.dtype)
            pool = dataclasses.replace(pool, position=new_pos,
                                       force_nnz=force_nnz)

        # ---------------- agent ops: behaviors ----------------
        ctx = StepContext(
            config=cfg, dt=dt, domain_lo=dlo, domain_hi=dhi,
            iteration=it, owned=owned_alive, neighbor_apply=nbr_apply,
            neighbor_results=nbr_results, params=rates,
            substance_gradient=(
                (lambda p: diff_ops.gradient(conc, p))
                if cfg.diffusion else (lambda p: jnp.zeros_like(p))),
            substance_value=(
                (lambda p: diff_ops.sample(conc, p))
                if cfg.diffusion else (lambda p: jnp.zeros(p.shape[:-1]))),
        )
        with _phase("behaviors"):
            birth_queues: List[Tuple[Dict[str, jnp.ndarray], jnp.ndarray]] = []
            death_mask = jnp.zeros((pool.capacity,), bool)
            for b, bk in zip(behaviors, bkeys):
                with jax.named_scope(b.name):
                    eff = b(ctx, pool, bk)
                if eff.set_channels:
                    ch = pool.channels()
                    for name, val in eff.set_channels.items():
                        # behaviors compute in f32/int32; storage keeps the
                        # pool's policy dtype (DtypePolicy, §4.3)
                        ch[name] = val.astype(ch[name].dtype)
                    pool = pool.with_channels(ch)
                if eff.birth_channels is not None:
                    birth_queues.append((eff.birth_channels, eff.birth_valid))
                if eff.death_mask is not None:
                    death_mask |= eff.death_mask
                if eff.secretion is not None and cfg.diffusion is not None:
                    conc = diff_ops.add_sources(conc, pool.position,
                                                eff.secretion)

        with _phase("statics"):
            # bookkeeping for the next static detection
            move_d = pool.position - pos0
            moved = jnp.sum(move_d * move_d, -1) > fp.move_eps ** 2
            grew = pool.diameter > dia0 + 1e-12
            pool = dataclasses.replace(pool, moved=moved & pool.alive,
                                       grew=grew & pool.alive)
            if use_cache:
                # budget spent this step: the max per-agent per-axis |Δposition|
                # (forces + behaviors) — the per-axis bound is what the widened
                # 3×3×3 stencil coverage argument consumes (grid.RebuildPolicy)
                step_disp = jnp.max(jnp.where(pool.alive[:, None],
                                              jnp.abs(move_d), 0.0))
                if pl is not None:
                    # the pair-list skin argument needs the EUCLIDEAN per-agent
                    # motion (a per-axis max does not bound ‖Δpos‖); the list
                    # stays a superset while 2·pair_disp ≤ skin
                    step_disp_eu = jnp.sqrt(jnp.max(jnp.where(
                        pool.alive, jnp.sum(move_d * move_d, -1), 0.0)))

        # ---------------- health watchdog (§7.5) ----------------
        # One fused reduction over channels the step already materialized;
        # evaluated before the commit phase so slot indices still line up
        # with force_arr/move_d. Observability only — supervisors act on it.
        with _phase("health"):
            health = stats.health
            if cfg.health is not None and cfg.health.any_enabled:
                health = health_mod.step_health(
                    cfg.health, owned_of(pool), pool.position, dlo, dhi,
                    force=force_arr, move_d=move_d)

        # ---------------- post standalone ops: commit ----------------
        with _phase("commit"):
            with _phase("deaths"):
                # ghosts are the neighbor shard's to kill — only owned
                # deaths commit
                death_mask &= owned_of(pool)
                deaths = jnp.sum((death_mask & pool.alive).astype(jnp.int32))
                pool = dataclasses.replace(pool,
                                           alive=pool.alive & ~death_mask)
                # n_active = force-computed agents still alive at iteration
                # end (counting at force time could exceed n_live after
                # deaths)
                n_active = (jnp.sum((active & pool.alive).astype(jnp.int32))
                            if active is not None
                            else jnp.sum(owned_of(pool).astype(jnp.int32)))
            with _phase("compaction"):
                pool = jax.lax.cond(deaths > 0, compaction.compact,
                                    lambda p: p, pool)

            births = jnp.zeros((), jnp.int32)
            birth_overflow = jnp.zeros((), jnp.int32)
            with _phase("births"):
                for q, valid in birth_queues:
                    if owned_channel is not None:
                        # newborns are committed — and later migrated if
                        # needed — by the shard that staged them
                        q = dict(q)
                        q["extra." + owned_channel] = jnp.ones_like(valid)
                    birth_overflow += compaction.birth_overflow(pool, valid)
                    births += jnp.sum(valid.astype(jnp.int32))
                    pool = compaction.commit_births(pool, q, valid, it)

            if use_cache:
                # deaths ran the compaction permutation and births appended live
                # tail slots — either way the cached tables no longer describe
                # the pool, so the next step must rebuild (never-stale-dead
                # invariant: stale tables only ever index the layout they were
                # built over, with every indexed slot still live)
                env = dataclasses.replace(
                    env,
                    steps_since=env.steps_since + 1,
                    disp_accum=env.disp_accum + step_disp,
                    dirty=(deaths > 0) | (births > 0),
                    **({"pairs": pairs,
                        "pair_disp": env.pair_disp + step_disp_eu}
                       if pl is not None else {}))

            n_live_end = jnp.sum(owned_of(pool).astype(jnp.int32))
        if colmap is not None:
            # a column block missing from K1's map means possibly-missed
            # pairs: the never-silent contract, with the width's own flag
            # and demand (DESIGN.md §4.2)
            work.update(
                colmap_overflow=colmap.overflow.astype(jnp.int32),
                colmap_demand=colmap.demand, k1_tiles=colmap.tiles,
                k1_live_rows=colmap.live_rows)
        stats = dataclasses.replace(
            stats, n_live=n_live_end,
            n_active=n_active, births=births, deaths=deaths,
            box_overflow=box_overflow, birth_overflow=birth_overflow,
            box_demand=box_demand,
            # slots needed to have committed every staged agent (§4.3
            # provenance: the capacity rung target)
            capacity_demand=n_live_end + birth_overflow,
            pair_overflow=pair_overflow, pair_demand=pair_demand,
            rebuilds=rebuilt, health=health, **work)
        return pool, conc, rng, stats, env

    return core


def stage_pool(capacity: int, behaviors: Sequence[Behavior], position,
               diameter=None, agent_type=None,
               extra_init: Dict[str, jnp.ndarray] | None = None,
               extra_specs: Dict[str, tuple] | None = None,
               policy: DtypePolicy | None = None) -> AgentPool:
    """Initial pool with every behavior's extra channels (both engines).

    ``extra_specs`` lets a caller add engine-owned channels on top (the
    distributed engine's ``owned`` flag); ``policy`` narrows auxiliary
    channel storage dtypes (DtypePolicy, §4.3)."""
    specs: Dict[str, tuple] = {}
    for b in behaviors:
        specs.update(b.extra_specs())
    if extra_specs:
        specs.update(extra_specs)
    position = jnp.asarray(position)
    pool = make_pool(capacity, position=position,
                     diameter=None if diameter is None else jnp.asarray(diameter),
                     agent_type=None if agent_type is None else jnp.asarray(agent_type),
                     extra_specs=specs, policy=policy)
    if extra_init:
        n = position.shape[0]
        for k, v in extra_init.items():
            arr = jnp.asarray(v).astype(pool.extra[k].dtype)
            pool.extra[k] = pool.extra[k].at[:n].set(arr)
    return pool


class Simulation:
    """Builds and runs the jitted iteration for a config + behavior list."""

    def __init__(self, config: EngineConfig, behaviors: Sequence[Behavior] = ()):
        self.config = config
        self.behaviors = list(behaviors)
        self.spec = config.grid_spec
        self._step_fn = jax.jit(self._build_step())

    # -- state construction -------------------------------------------------
    def init_state(self, position, diameter=None, agent_type=None,
                   extra_init: Dict[str, jnp.ndarray] | None = None,
                   seed: int = 0) -> EngineState:
        pool = stage_pool(self.config.capacity, self.behaviors, position,
                          diameter, agent_type, extra_init,
                          policy=self.config.dtypes)
        dspec = self.config.diffusion
        conc = jnp.zeros(dspec.dims, jnp.float32) if dspec else jnp.zeros((1, 1, 1))
        env = None
        if self.config.rebuild.mode == "every_k":
            env = grid_mod.initial_rebuild_state(
                self.spec, self.config.capacity,
                jnp.asarray(self.config.domain_lo, jnp.float32),
                jnp.asarray(self.config.cell_size, jnp.float32),
                pairlist=self.config.pairlist)
        return EngineState(pool=pool, conc=conc, rng=jax.random.PRNGKey(seed),
                           iteration=jnp.zeros((), jnp.int32),
                           stats=StepStats.zeros(), env=env)

    # -- the iteration -------------------------------------------------------
    def _build_step(self):
        core = make_iteration_core(self.config, self.behaviors)

        def step(state: EngineState) -> EngineState:
            pool, conc, rng, stats, env = core(state.pool, state.conc,
                                               state.rng, state.iteration,
                                               state.env)
            return EngineState(pool=pool, conc=conc, rng=rng,
                               iteration=state.iteration + 1, stats=stats,
                               env=env)

        return step

    # -- public API ----------------------------------------------------------
    def step(self, state: EngineState) -> EngineState:
        return self._step_fn(state)

    def run(self, state: EngineState, n_iterations: int,
            callback: Callable[[int, EngineState], None] | None = None,
            check_overflow: bool = False) -> EngineState:
        """Run ``n_iterations``. With ``check_overflow`` the host checks the
        overflow flags each iteration and raises — the engine never
        silently drops interactions (DESIGN.md §4.2 fallback contract); callers
        respond by raising the capacity the message names: ``max_per_run`` /
        ``max_per_box``, ``capacity``, ``pairlist.max_pairs`` or
        ``colmap_width`` (a recompile, mirroring BioDynaMo's dynamic grid
        growth)."""
        for i in range(n_iterations):
            # host spans on the profiler's clock (free unless it traces)
            with jax.profiler.TraceAnnotation("sim.step", iteration=i):
                state = self._step_fn(state)
            if check_overflow:
                with jax.profiler.TraceAnnotation("sim.overflow_check"):
                    counts = state.stats.totals(StepStats.OVERFLOW_FIELDS
                                                + StepStats.WORK_FIELDS)
                telemetry.record_step(
                    {f: counts[f] for f in StepStats.WORK_FIELDS})
                flags = {f for f in StepStats.OVERFLOW_FIELDS if counts[f]}
                if "box_overflow" in flags:
                    if self.config.environment == "hash_grid":
                        raise RuntimeError(
                            f"iteration {i}: hash bucket overflow (a bucket "
                            f"holds > {grid_mod.HASH_K_MULT}×max_per_box = "
                            f"{grid_mod.HASH_K_MULT * self.spec.max_per_box} "
                            f"agents); raise EngineConfig.max_per_box")
                    raise RuntimeError(
                        f"iteration {i}: grid run overflow (a 3-box z-run "
                        f"holds > {self.spec.run_capacity} agents); raise "
                        f"EngineConfig.max_per_run / max_per_box")
                if "birth_overflow" in flags:
                    raise RuntimeError(
                        f"iteration {i}: birth overflow; raise EngineConfig.capacity")
                if "pair_overflow" in flags:
                    raise RuntimeError(
                        f"iteration {i}: pair-list overflow (an agent has > "
                        f"{self.config.pairlist.max_pairs} in-range(+skin) "
                        f"candidates); raise PairListConfig.max_pairs")
                if "colmap_overflow" in flags:
                    raise RuntimeError(
                        f"iteration {i}: K1 column-map overflow (a 128-row "
                        f"block needs {int(state.stats.colmap_demand)} column "
                        f"blocks > the map's width "
                        f"{self.config.colmap_capacity}); raise "
                        f"EngineConfig.colmap_width")
            if callback is not None:
                callback(i, state)
        return state

    def run_supervised(self, state: EngineState, n_iterations: int,
                       ckpt_dir: str, **kwargs):
        """Run under the fault-tolerant supervisor (simcheck, §7.5).

        Convenience wrapper: wraps this config/behaviors in a
        ``CapacityLadder`` and delegates to ``simcheck.SupervisedRunner`` —
        checkpoints every ``checkpoint_every`` steps, rolls back to the last
        checkpoint on a health fault or ladder exhaustion, and retries under
        the degradation policy. Returns ``(state, RunReport)``.
        """
        from . import simcheck
        runner = simcheck.SupervisedRunner(
            CapacityLadder(self.config, self.behaviors), ckpt_dir, **kwargs)
        return runner.run(state, n_iterations)


# ---------------------------------------------------------------------------
# Capacity ladder (DESIGN.md §4.3) — automatic pool growth across rungs
# ---------------------------------------------------------------------------

class CapacityExhausted(RuntimeError):
    """The ladder hit ``max_capacity`` — structured, so supervisors recover.

    Unlike a bare RuntimeError, the exception carries the last-good pre-step
    state and its final ``StepStats`` (attached by ``LadderDriverBase.step``
    before re-raising), so a supervisor (simcheck.SupervisedRunner) can
    checkpoint the trajectory and retry under a degradation policy instead of
    losing the run (§7.5).
    """

    def __init__(self, message: str, demand: int = 0, rung: int = 0,
                 max_capacity: Optional[int] = None):
        super().__init__(message)
        self.demand = demand
        self.rung = rung
        self.max_capacity = max_capacity
        self.state = None      # last-good pre-step state (driver attaches)
        self.stats = None      # StepStats of the overflowing execution
        self.iteration = None  # iteration index the state is rewound to


@dataclasses.dataclass(frozen=True)
class LadderConfig:
    """How the capacity ladder grows on overflow.

    growth_factor:      geometric rung ratio (BioDynaMo's pool allocator
                        grows block counts geometrically for the same
                        amortization argument, paper §4.3).
    max_capacity:       hard ceiling on pool capacity; exceeding it raises
                        instead of growing (never silent).
    max_grows_per_step: safety bound on grow→re-run cycles for ONE iteration
                        (a scenario whose demand outruns geometric growth
                        this badly is a config bug, not a ladder job).
    round_to:           capacities round up to a multiple of this (keeps
                        rung shapes block-aligned for the query loops).
    """

    growth_factor: float = 2.0
    max_capacity: Optional[int] = None
    max_grows_per_step: int = 16
    round_to: int = 64


def next_rung(old: int, demand: int, factor: float, round_to: int = 1) -> int:
    """Smallest geometric rung ≥ demand (always at least one rung up)."""
    new = max(int(math.ceil(old * factor)), old + 1)
    while new < demand:
        new = int(math.ceil(new * factor))
    return -(-new // round_to) * round_to


def colmap_rung(cfg: EngineConfig, demand: int, factor: float) -> int:
    """K1's next column-map width for ``demand`` column blocks: the next
    geometric rung, held to one SMEM slice (SMEM_TABLE_ENTRIES entries: a
    launch then covers one row block). A demand beyond that raises
    ``CapacityExhausted``; none can arise in a pool of at most 2**22 slots,
    which has no more column blocks than that."""
    from ..kernels import collision_force as k1
    if demand > k1.SMEM_TABLE_ENTRIES:
        raise CapacityExhausted(
            f"column-map demand {demand} exceeds one SMEM slice "
            f"({k1.SMEM_TABLE_ENTRIES} entries)", demand=demand,
            rung=demand, max_capacity=k1.SMEM_TABLE_ENTRIES)
    return min(next_rung(cfg.colmap_capacity, demand, factor),
               k1.SMEM_TABLE_ENTRIES)


class LadderDriverBase:
    """The overflow→grow→re-run loop shared by both ladder drivers.

    Subclass contract: ``self._sim`` is the current-rung engine (anything
    with a jitted ``step``), ``_diagnose(stats)`` returns the next-rung
    config or None (raising on non-growable flags), and
    ``_grow(new_cfg, prev_state, iteration)`` rebuilds the engine at the new
    rung and returns the (possibly restaged) pre-step state to re-run.
    """

    ladder: "LadderConfig"

    def _iter_of(self, state) -> int:
        """Scalar step index for logging/rewind bookkeeping. The ensemble
        driver overrides this (its ``iteration`` is a per-lane vector; the
        global tick is the scalar a rewind rewinds to)."""
        return int(state.iteration)

    def step(self, state):
        """One iteration with automatic growth (rewinds the step on overflow).

        The overflowing execution dropped work (newborns, candidate pairs),
        so its output is discarded and the iteration re-runs from its
        pre-step state at the new rung — never resumed from.

        The input ``state`` is CONSUMED: on a growing step its pool buffers
        are donated to the restage (compaction.grow_channels), so on
        backends with donation support (not CPU) a caller-held reference to
        ``state`` may point at deleted arrays afterwards. Treat ``step`` as
        taking ownership, exactly like stepping a jitted function with
        donated arguments."""
        prev = state
        state = self._sim.step(prev)
        grows = 0
        while True:
            try:
                new_cfg = self._diagnose(state.stats)  # host sync on flags
            except CapacityExhausted as e:
                # annotate with the last-good pre-step state so supervisors
                # can checkpoint-and-degrade instead of losing the run
                e.state = prev
                e.stats = state.stats
                e.iteration = self._iter_of(prev)
                raise
            if new_cfg is None:
                return state
            grows += 1
            if grows > self.ladder.max_grows_per_step:
                raise RuntimeError(
                    f"iteration {self._iter_of(prev)}: still overflowing "
                    f"after {grows - 1} grows — demand outruns "
                    f"growth_factor={self.ladder.growth_factor}")
            prev = self._grow(new_cfg, prev, self._iter_of(prev))
            state = self._sim.step(prev)

    def run(self, state, n_iterations: int,
            callback: Callable | None = None):
        for i in range(n_iterations):
            state = self.step(state)
            if callback is not None:
                callback(i, state)
        return state

    def _log_rungs(self, iteration: int, triples) -> None:
        """Record (field, old, new) growth events + count the recompile."""
        for field, old, new in triples:
            if old != new:
                self.rungs.append({"iteration": iteration, "field": field,
                                   "old": old, "new": new})
        self.recompiles += 1


class CapacityLadder(LadderDriverBase):
    """Host-side driver: `Simulation.run` with automatic capacity growth.

    The paper's custom heap (§4.3) lets populations grow without per-agent
    allocation cost; under jit every shape is static, so the JAX-idiom
    analog is a *ladder of fixed-shape pools*: run the jitted iteration
    core, watch the never-silent overflow flags (StepStats), and when one
    fires, grow the affected capacity geometrically, re-stage the pool into
    the larger shape (buffer donation bounds peak memory), recompile, and
    **re-run the very iteration that overflowed** from its pre-step state.
    The rewind is what makes trajectories bit-identical to a pre-sized
    pool: the overflowing step dropped work (newborns, candidate pairs),
    so its output is discarded, never resumed from.

    Which knob grows is read off the stats provenance:

      birth_overflow  → ``capacity``       (rung target: capacity_demand)
      box_overflow    → ``max_per_run``    (uniform grid; target box_demand)
                        ``max_per_box``    (hash grid bucket width)
      pair_overflow   → ``pairlist.max_pairs`` (Verlet list row width;
                        rung target: pair_demand)
      colmap_overflow → ``colmap_width``   (K1's column-map width; rung
                        target: colmap_demand, at most one SMEM slice,
                        colmap_rung), where the demand passed the width;
                        else a z-run outran K1's span, which box_overflow
                        flags too and ``max_per_run`` clears

    Growth events are recorded in ``self.rungs`` and recompiles counted in
    ``self.recompiles``.
    """

    def __init__(self, config: EngineConfig, behaviors: Sequence[Behavior] = (),
                 ladder: LadderConfig | None = None):
        self.ladder = ladder or LadderConfig()
        self.behaviors = list(behaviors)
        self.config = config
        self.rungs: List[Dict] = []
        self.recompiles = 0
        self._sim = Simulation(config, self.behaviors)

    @property
    def sim(self) -> Simulation:
        """The current-rung Simulation (rebuilt at every grow)."""
        return self._sim

    def init_state(self, *args, **kwargs) -> EngineState:
        return self._sim.init_state(*args, **kwargs)

    # -- growth policy -------------------------------------------------------
    def _diagnose(self, stats: StepStats) -> Optional[EngineConfig]:
        """New config for the overflow recorded in ``stats`` (None = no grow)."""
        cfg, lad = self.config, self.ladder
        changes: Dict = {}
        if int(stats["pair_overflow"]):
            demand = int(stats["pair_demand"])
            changes["pairlist"] = dataclasses.replace(
                cfg.pairlist,
                max_pairs=next_rung(cfg.pairlist.max_pairs, demand,
                                    lad.growth_factor))
        if int(stats["box_overflow"]):
            demand = int(stats["box_demand"])
            if cfg.environment == "hash_grid":
                need = -(-demand // grid_mod.HASH_K_MULT)
                changes["max_per_box"] = next_rung(
                    cfg.max_per_box, need, lad.growth_factor)
            else:
                cur = cfg.grid_spec.run_capacity
                changes["max_per_run"] = next_rung(
                    cur, demand, lad.growth_factor)
        if int(stats["colmap_overflow"]):
            if int(stats["colmap_demand"]) > cfg.colmap_capacity:
                changes["colmap_width"] = colmap_rung(
                    cfg, int(stats["colmap_demand"]), lad.growth_factor)
            elif "max_per_run" not in changes:
                # the map fit its width, so a z-run outran K1's span, which
                # only a run over the run capacity can (ops.colmap_span)
                raise RuntimeError(
                    "K1 column-map span overflow without a grid run "
                    "overflow: the span no longer follows the run capacity")
        if int(stats["birth_overflow"]):
            demand = int(stats["capacity_demand"])
            new_cap = next_rung(cfg.capacity, demand, lad.growth_factor,
                                lad.round_to)
            if lad.max_capacity is not None and new_cap > lad.max_capacity:
                raise CapacityExhausted(
                    f"capacity ladder exhausted: demand {demand} needs rung "
                    f"{new_cap} > max_capacity={lad.max_capacity}",
                    demand=demand, rung=new_cap,
                    max_capacity=lad.max_capacity)
            changes["capacity"] = new_cap
        if not changes:
            return None
        return dataclasses.replace(cfg, **changes)

    def _grow(self, new_cfg: EngineConfig, prev: EngineState,
              iteration: int) -> EngineState:
        rungs = [(f, getattr(self.config, f), getattr(new_cfg, f))
                 for f in ("capacity", "max_per_box", "max_per_run")]
        rungs.append(("colmap_width", self.config.colmap_capacity,
                      new_cfg.colmap_capacity))
        if new_cfg.pairlist is not None and self.config.pairlist is not None:
            rungs.append(("max_pairs", self.config.pairlist.max_pairs,
                          new_cfg.pairlist.max_pairs))
        self._log_rungs(iteration, rungs)
        old_cfg, self.config = self.config, new_cfg
        self._sim = Simulation(new_cfg, self.behaviors)
        cap_grew = new_cfg.capacity != prev.pool.capacity
        pairs_grew = (new_cfg.pairlist is not None
                      and old_cfg.pairlist is not None
                      and (cap_grew or new_cfg.pairlist.max_pairs
                           != old_cfg.pairlist.max_pairs))
        if cap_grew or pairs_grew:
            env = prev.env
            if env is not None:
                # the rewound step re-runs with this cache: growing it the
                # way a pre-sized build would have laid it out keeps the
                # grown trajectory bit-identical (grid.grow_grid_state /
                # grid.grow_pairlist — a cached list that overflowed never
                # survives a kept step, so zero-padding matches a pre-sized
                # build exactly)
                if cap_grew:
                    env = dataclasses.replace(
                        env, grid=grid_mod.grow_grid_state(env.grid,
                                                           new_cfg.capacity))
                if pairs_grew and env.pairs is not None:
                    env = dataclasses.replace(
                        env, pairs=grid_mod.grow_pairlist(
                            env.pairs, new_cfg.capacity,
                            new_cfg.pairlist.max_pairs))
            pool = (compaction.grow_pool(prev.pool, new_cfg.capacity)
                    if cap_grew else prev.pool)
            prev = dataclasses.replace(prev, pool=pool, env=env)
        return prev
