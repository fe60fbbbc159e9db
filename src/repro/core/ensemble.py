"""Ensemble engine — one device steps L independent simulations in lockstep.

ABM users run *sweeps*, not single trajectories (calibration, uncertainty
quantification, epidemic what-ifs — ROADMAP "Simulation-as-a-service"), and a
sweep member is typically small: hundreds of lanes of a few hundred agents,
not one lane of millions. The C++ lineage schedules such sweeps as separate
processes; a JAX engine can do something structurally better — ``jax.vmap``
the *whole Algorithm-1 iteration core* over a leading lane axis, so one XLA
program advances every member per step (DESIGN.md §8):

  * **Per-lane everything.** RNG keys, ``ScenarioParams`` (traced dt / force
    constants / behavior rates — engine.py), iteration counters, and
    ``StepStats`` all carry a leading ``(L,)`` axis. Lane *i*'s trajectory is
    bit-exact vs a solo :class:`~.engine.Simulation` run with the same
    seed/params (tests/test_ensemble.py): the SIR core is elementwise float +
    integer/boolean reduction work, which XLA:CPU maps over the lane axis
    without reassociating per-lane arithmetic.

  * **Lane masking.** ``active`` is a ``(L,)`` bool mask. Inactive lanes
    still ride through the vmapped compute (dense batched math has no
    data-dependent skip), but every write is frozen via ``jnp.where`` and
    their stats are zeroed — a retired lane holds its final state bit-for-bit
    until the service overwrites it. The economics are those of continuous
    batching: an idle lane costs its batch slot, so the service's job is to
    keep lanes full, not to make idle lanes free.

  * **Shared-rung ladder.** Capacity knobs (pool capacity, run width,
    pair-list width) stay *shared* across lanes — one rung, one compiled
    program. :class:`EnsembleCapacityLadder` sizes the next rung off the
    worst per-lane demand and rewinds the overflowing tick, the same
    max-over-members + rewind argument the distributed ladder makes per
    shard (distributed.py): the overflowing execution dropped work, so its
    output is discarded and the tick re-runs at the new rung — bit-identical
    to a pre-sized ensemble.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import grid as grid_mod
from .agents import AgentPool
from .behaviors import Behavior
from .engine import (CapacityExhausted, EngineConfig, EngineState,
                     LadderConfig, LadderDriverBase, ScenarioParams,
                     Simulation, colmap_rung, make_iteration_core,
                     next_rung)
from .stats import StepStats


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EnsembleState:
    """State of L lanes advancing in lockstep. Leading axis of every array
    leaf is the lane axis; ``tick`` is the global ensemble step counter
    (per-lane ``iteration`` counters advance only while the lane is active,
    so they match the solo trajectory the lane reproduces)."""

    pool: AgentPool                      # channels (L, C, ...)
    conc: jnp.ndarray                    # (L, ...) diffusion grids
    rng: jax.Array                       # (L, 2) per-lane threefry keys
    iteration: jnp.ndarray               # (L,) int32 per-lane step index
    stats: StepStats                     # (L,) per-lane counters
    active: jnp.ndarray                  # (L,) bool lane mask
    params: Optional[ScenarioParams]     # per-lane knobs, leaves (L, ...)
    tick: jnp.ndarray                    # () int32 ensemble step counter
    env: Optional[grid_mod.RebuildState] = None
                                         # per-lane rebuild caches (L, ...)

    @property
    def n_lanes(self) -> int:
        return self.active.shape[0]


def make_ensemble_core(config: EngineConfig,
                       behaviors: Sequence[Behavior] = ()):
    """vmap of :func:`~.engine.make_iteration_core` over a leading lane axis.

    Returns ``ecore(pool, conc, rng, iteration, active, env, params) ->
    (pool, conc, rng, stats, env)`` where every argument/result carries a
    leading ``(L,)`` lane axis (``env``/``params`` may be None, matching the
    solo core). Lanes with ``active=False`` are frozen: their state passes
    through unchanged and their stats are zeroed, so a retired lane can
    neither drift nor trip the ladder/health machinery.
    """
    core = make_iteration_core(config, behaviors)

    def ecore(pool: AgentPool, conc: jnp.ndarray, rng: jax.Array,
              iteration: jnp.ndarray, active: jnp.ndarray,
              env: Optional[grid_mod.RebuildState] = None,
              params: Optional[ScenarioParams] = None):
        def one(pool, conc, rng, it, env, params):
            return core(pool, conc, rng, it, env, params)

        npool, nconc, nrng, stats, nenv = jax.vmap(one)(
            pool, conc, rng, iteration, env, params)

        def freeze(new, old):
            act = active.reshape(active.shape + (1,) * (new.ndim - 1))
            return jnp.where(act, new, old)

        tm = jax.tree_util.tree_map
        pool = tm(freeze, npool, pool)
        conc = tm(freeze, nconc, conc)
        rng = tm(freeze, nrng, rng)
        if env is not None:
            env = tm(freeze, nenv, env)
        stats = tm(lambda s: jnp.where(active, s, 0).astype(s.dtype), stats)
        return pool, conc, rng, stats, env

    return ecore


def grow_stacked_pool(pool: AgentPool, new_capacity: int) -> AgentPool:
    """Grow stacked ``(L, C, ...)`` pool channels to a larger capacity.

    Lane-axis analog of ``compaction.grow_channels``: new slots
    ``[C, new_capacity)`` are zero-filled (dead), exactly like the tail of a
    freshly staged pool, so the ladder's rewound trajectory matches a
    pre-sized ensemble bit for bit."""
    old = next(iter(pool.channels().values())).shape[1]
    if new_capacity < old:
        raise ValueError(f"cannot shrink pool {old} -> {new_capacity}")
    if new_capacity == old:
        return pool
    ch = {}
    for k, v in pool.channels().items():
        pad = jnp.zeros((v.shape[0], new_capacity - old) + v.shape[2:],
                        v.dtype)
        ch[k] = jnp.concatenate([v, pad], axis=1)
    return pool.with_channels(ch)


class EnsembleEngine:
    """L-lane ensemble of one EngineConfig — jitted lockstep step + lane IO.

    ``params_template`` fixes the per-lane :class:`ScenarioParams` pytree
    *structure* (key sets are static under jit); pass e.g.
    ``ScenarioParams.of(beta=0.0)`` and every admit supplies a same-structure
    instance. ``None`` means no per-lane knobs (all lanes share the static
    config — seeds still differ per lane).
    """

    def __init__(self, config: EngineConfig,
                 behaviors: Sequence[Behavior] = (), n_lanes: int = 1,
                 params_template: Optional[ScenarioParams] = None):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        self.config = config
        self.behaviors = list(behaviors)
        self.n_lanes = n_lanes
        self.params_template = params_template
        self._solo = Simulation(config, self.behaviors)
        self._step_fn = jax.jit(self._build_step())
        self._write_fn = jax.jit(self._write_lane)
        self._retire_fn = jax.jit(self._set_active, static_argnums=2)

    # -- lane staging --------------------------------------------------------
    def stage_lane(self, position, diameter=None, agent_type=None,
                   extra_init: Optional[Dict[str, jnp.ndarray]] = None,
                   seed: int = 0) -> EngineState:
        """A solo-engine initial state, ready to admit into a lane."""
        return self._solo.init_state(position, diameter, agent_type,
                                     extra_init, seed=seed)

    def blank_lane(self) -> EngineState:
        """An idle lane: empty pool (no live agents), fresh dirty cache."""
        return self._solo.init_state(jnp.zeros((0, 3), jnp.float32))

    def init_state(self) -> EnsembleState:
        """All-idle ensemble: every lane blank and inactive."""
        L = self.n_lanes
        lane = self.blank_lane()
        bcast = lambda a: jnp.broadcast_to(a[None], (L,) + a.shape)
        tm = jax.tree_util.tree_map
        params = None
        if self.params_template is not None:
            params = tm(lambda a: bcast(jnp.asarray(a)),
                        self.params_template)
        return EnsembleState(
            pool=tm(bcast, lane.pool), conc=bcast(lane.conc),
            rng=bcast(lane.rng),
            iteration=jnp.zeros((L,), jnp.int32),
            stats=StepStats.zeros((L,)),
            active=jnp.zeros((L,), bool), params=params,
            tick=jnp.zeros((), jnp.int32),
            env=None if lane.env is None else tm(bcast, lane.env))

    # -- the lockstep iteration ---------------------------------------------
    def _build_step(self):
        ecore = make_ensemble_core(self.config, self.behaviors)

        def step(state: EnsembleState) -> EnsembleState:
            pool, conc, rng, stats, env = ecore(
                state.pool, state.conc, state.rng, state.iteration,
                state.active, state.env, state.params)
            return EnsembleState(
                pool=pool, conc=conc, rng=rng,
                iteration=jnp.where(state.active, state.iteration + 1,
                                    state.iteration),
                stats=stats, active=state.active, params=state.params,
                tick=state.tick + 1, env=env)

        return step

    def step(self, state: EnsembleState) -> EnsembleState:
        return self._step_fn(state)

    # -- lane admit / retire (jitted; lane index traced → one compile) ------
    def _write_lane(self, state: EnsembleState, lane: jnp.ndarray,
                    lane_state: EngineState,
                    params: Optional[ScenarioParams]) -> EnsembleState:
        tm = jax.tree_util.tree_map
        wr = lambda e, l: e.at[lane].set(l)
        new_params = state.params
        if params is not None:
            new_params = tm(wr, state.params, params)
        return EnsembleState(
            pool=tm(wr, state.pool, lane_state.pool),
            conc=wr(state.conc, lane_state.conc),
            rng=wr(state.rng, lane_state.rng),
            iteration=state.iteration.at[lane].set(lane_state.iteration),
            stats=state.stats,
            active=state.active.at[lane].set(True),
            params=new_params, tick=state.tick,
            env=(state.env if state.env is None
                 else tm(wr, state.env, lane_state.env)))

    def admit(self, state: EnsembleState, lane, lane_state: EngineState,
              params: Optional[ScenarioParams] = None) -> EnsembleState:
        """Write a solo state into lane ``lane`` and mark it active."""
        if (params is None) != (self.params_template is None):
            raise ValueError(
                "admit params must match the engine's params_template "
                f"(template {'set' if self.params_template is not None else 'None'}, "
                f"got {'params' if params is not None else 'None'})")
        return self._write_fn(state, jnp.asarray(lane, jnp.int32),
                              lane_state, params)

    def _set_active(self, state: EnsembleState, lane: jnp.ndarray,
                    value: bool) -> EnsembleState:
        return dataclasses.replace(
            state, active=state.active.at[lane].set(value))

    def retire(self, state: EnsembleState, lane) -> EnsembleState:
        """Deactivate lane ``lane`` — its state freezes (readable until the
        next admit overwrites it)."""
        return self._retire_fn(state, jnp.asarray(lane, jnp.int32), False)

    def read_lane(self, state: EnsembleState, lane: int) -> EngineState:
        """Lane ``lane``'s state as a solo EngineState (host-side readout)."""
        tm = jax.tree_util.tree_map
        take = lambda a: a[lane]
        return EngineState(
            pool=tm(take, state.pool), conc=state.conc[lane],
            rng=state.rng[lane], iteration=state.iteration[lane],
            stats=tm(take, state.stats),
            env=None if state.env is None else tm(take, state.env))


class EnsembleCapacityLadder(LadderDriverBase):
    """Capacity ladder over an ensemble: shared rungs, worst-lane demand.

    One compiled program serves every lane, so capacity knobs cannot differ
    per lane — the next rung is sized off ``max`` over the per-lane demand
    vectors (the distributed ladder's agreed-global-rung argument, one lane
    standing in for one shard) and the overflowing tick is re-run from its
    pre-step state at the new rung. Because the overflowing execution
    dropped work, discarding its output keeps every lane's trajectory
    bit-identical to a pre-sized ensemble.
    """

    def __init__(self, config: EngineConfig,
                 behaviors: Sequence[Behavior] = (), n_lanes: int = 1,
                 params_template: Optional[ScenarioParams] = None,
                 ladder: Optional[LadderConfig] = None):
        self.ladder = ladder or LadderConfig()
        self.behaviors = list(behaviors)
        self.config = config
        self.n_lanes = n_lanes
        self.params_template = params_template
        self.rungs: List[Dict] = []
        self.recompiles = 0
        self._sim = EnsembleEngine(config, self.behaviors, n_lanes,
                                   params_template)

    @property
    def engine(self) -> EnsembleEngine:
        """The current-rung EnsembleEngine (rebuilt at every grow)."""
        return self._sim

    def init_state(self) -> EnsembleState:
        return self._sim.init_state()

    def _iter_of(self, state: EnsembleState) -> int:
        return int(state.tick)

    # -- growth policy -------------------------------------------------------
    def _diagnose(self, stats: StepStats) -> Optional[EngineConfig]:
        cfg, lad = self.config, self.ladder
        tot = lambda f: int(np.asarray(jnp.sum(stats[f])))
        peak = lambda f: int(np.asarray(jnp.max(stats[f])))
        changes: Dict = {}
        if tot("pair_overflow"):
            changes["pairlist"] = dataclasses.replace(
                cfg.pairlist, max_pairs=next_rung(
                    cfg.pairlist.max_pairs, peak("pair_demand"),
                    lad.growth_factor))
        if tot("box_overflow"):
            demand = peak("box_demand")
            if cfg.environment == "hash_grid":
                need = -(-demand // grid_mod.HASH_K_MULT)
                changes["max_per_box"] = next_rung(
                    cfg.max_per_box, need, lad.growth_factor)
            else:
                changes["max_per_run"] = next_rung(
                    cfg.grid_spec.run_capacity, demand, lad.growth_factor)
        if tot("colmap_overflow"):
            changes["colmap_width"] = colmap_rung(
                cfg, peak("colmap_demand"), lad.growth_factor)
        if tot("birth_overflow"):
            demand = peak("capacity_demand")
            new_cap = next_rung(cfg.capacity, demand, lad.growth_factor,
                                lad.round_to)
            if lad.max_capacity is not None and new_cap > lad.max_capacity:
                raise CapacityExhausted(
                    f"ensemble capacity ladder exhausted: worst-lane demand "
                    f"{demand} needs rung {new_cap} > "
                    f"max_capacity={lad.max_capacity}", demand=demand,
                    rung=new_cap, max_capacity=lad.max_capacity)
            changes["capacity"] = new_cap
        if not changes:
            return None
        return dataclasses.replace(cfg, **changes)

    def _grow(self, new_cfg: EngineConfig, prev: EnsembleState,
              iteration: int) -> EnsembleState:
        rungs = [(f, getattr(self.config, f), getattr(new_cfg, f))
                 for f in ("capacity", "max_per_box", "max_per_run")]
        rungs.append(("colmap_width", self.config.colmap_capacity,
                      new_cfg.colmap_capacity))
        if new_cfg.pairlist is not None and self.config.pairlist is not None:
            rungs.append(("max_pairs", self.config.pairlist.max_pairs,
                          new_cfg.pairlist.max_pairs))
        self._log_rungs(iteration, rungs)
        old_cfg, self.config = self.config, new_cfg
        self._sim = EnsembleEngine(new_cfg, self.behaviors, self.n_lanes,
                                   self.params_template)
        cap_grew = new_cfg.capacity != old_cfg.capacity
        pairs_grew = (new_cfg.pairlist is not None
                      and old_cfg.pairlist is not None
                      and (cap_grew or new_cfg.pairlist.max_pairs
                           != old_cfg.pairlist.max_pairs))
        if cap_grew or pairs_grew:
            env = prev.env
            if env is not None:
                # same rewind-parity argument as the solo/distributed
                # ladders: grow_grid_state / grow_pairlist pad trailing axes
                # only, so the (L, ...) lane caches extend exactly as L
                # pre-sized builds would have (grid.py)
                if cap_grew:
                    env = dataclasses.replace(
                        env, grid=grid_mod.grow_grid_state(env.grid,
                                                           new_cfg.capacity))
                if pairs_grew and env.pairs is not None:
                    env = dataclasses.replace(
                        env, pairs=grid_mod.grow_pairlist(
                            env.pairs, new_cfg.capacity,
                            new_cfg.pairlist.max_pairs))
            pool = (grow_stacked_pool(prev.pool, new_cfg.capacity)
                    if cap_grew else prev.pool)
            prev = dataclasses.replace(prev, pool=pool, env=env)
        return prev
