"""Simulation checkpoint/resume and the supervised run loop (DESIGN.md §7.5).

The train side already had fault tolerance (train/checkpoint.py: atomic
tmp-then-rename saves, async writer, GC); this module puts the *simulation*
run state through the same writer and builds the recovery logic on top:

  * ``save_state`` / ``restore_state`` — the complete single-device run
    state (pool SoA channels, capacity-stable RNG key, RebuildPolicy cache,
    step index, stats) as one pytree checkpoint. Restores are **bit-exact**:
    every leaf round-trips through npz losslessly (binary float storage),
    the manifest records the rung and degradation knobs in effect so the
    resuming process rebuilds the *same* jit program, and the iteration core
    is deterministic — so a resumed run replays the uninterrupted
    trajectory byte for byte (the same argument the ladder rewind proves).

  * ``save_dist_state`` / ``restore_dist_state`` — the distributed
    counterpart. Channels are already global ``(n_shards·local, ...)``
    arrays, so one checkpoint holds every shard's slab; the manifest records
    the topology. Restoring onto the **same** shard count is bit-exact (and
    a differing ``local_capacity`` rung re-packs slabs via
    ``compaction.repack_slabs``, the ladder's own restage). Restoring onto a
    **different** shard count re-partitions live agents through the init
    path (quantile boundaries + ``partition_global``) — a valid state, but a
    different slab layout, so only same-topology resumes claim bit-exactness.

  * ``SupervisedRunner`` — the run loop that survives faults: checkpoints
    every ``checkpoint_every`` steps, reads the in-graph health bitmask
    (``StepStats.health``, core/health.py) after each step, and on a health
    fault or ladder exhaustion (``CapacityExhausted``) rolls back to the
    last checkpoint and retries under a ``DegradationPolicy`` — forcing
    every-step grid rebuilds, dropping the fused/Pallas sweep to the
    sequential XLA path (bit-exact per tests/test_fused.py, so recovery
    itself does not perturb the trajectory), and finally shrinking dt. Every
    intervention lands in a structured ``RunReport`` instead of a dead run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..train import checkpoint as ckpt_mod
from . import compaction, grid as grid_mod
from .behaviors import Behavior
from .distributed import (DistConfig, DistState, DistributedCapacityLadder,
                          DistributedSimulation, OWNED, partition_global,
                          quantile_boundaries)
from .engine import (CapacityExhausted, CapacityLadder, EngineConfig,
                     EngineState, ScenarioParams, Simulation, stage_pool)
from .ensemble import EnsembleEngine, EnsembleState
from .health import HealthFault, describe
from .stats import StepStats

_FORMAT = 1            # manifest extras schema version


# ---------------------------------------------------------------------------
# Knob snapshots — what the arrays alone cannot carry
# ---------------------------------------------------------------------------

def _engine_knobs(cfg: EngineConfig) -> Dict:
    """The config knobs a resume must reproduce: rung sizes (array shapes
    depend on them) and the degradation-ladder knobs (trajectory depends on
    them)."""
    return {"capacity": cfg.capacity,
            "max_per_box": cfg.max_per_box,
            "max_per_run": cfg.max_per_run,
            "colmap_width": cfg.colmap_width,
            "dt": cfg.dt,
            "fused_sweep": cfg.fused_sweep,
            "force_impl": cfg.force_impl,
            "rebuild": {"mode": cfg.rebuild.mode, "k": cfg.rebuild.k,
                        "displacement_bound": cfg.rebuild.displacement_bound}}


def _apply_engine_knobs(cfg: EngineConfig, knobs: Dict,
                        mode: str) -> EngineConfig:
    """Apply recorded knobs onto ``cfg``.

    mode="all":   rungs + degradation knobs — a plain resume reproduces the
                  exact program the checkpoint ran under (bit-exact).
    mode="rungs": rung sizes only — the supervisor's rollback path, which
                  must keep its *degraded* dt/sweep/rebuild knobs rather
                  than have the checkpoint resurrect the faulty ones.
    """
    if mode not in ("all", "rungs"):
        raise ValueError(f"apply_knobs must be 'all' or 'rungs', got {mode!r}")
    changes: Dict[str, Any] = {k: knobs[k] for k in
                               ("capacity", "max_per_box", "max_per_run")}
    # manifests written before the column map had its own rung lack it
    changes["colmap_width"] = knobs.get("colmap_width", cfg.colmap_width)
    if mode == "all":
        changes.update(dt=knobs["dt"], fused_sweep=knobs["fused_sweep"],
                       force_impl=knobs["force_impl"],
                       rebuild=grid_mod.RebuildPolicy(**knobs["rebuild"]))
    return dataclasses.replace(cfg, **changes)


def _dist_knobs(dcfg: DistConfig) -> Dict:
    return {"n_shards": dcfg.n_shards,
            "local_capacity": dcfg.local_capacity,
            "halo_capacity": dcfg.halo_capacity,
            "migrate_capacity": dcfg.migrate_capacity,
            "rebalance_frequency": dcfg.rebalance_frequency,
            "engine": _engine_knobs(dcfg.engine)}


def _apply_dist_knobs(dcfg: DistConfig, knobs: Dict, mode: str) -> DistConfig:
    eng = _apply_engine_knobs(dcfg.engine, knobs["engine"], mode)
    return dataclasses.replace(
        dcfg, engine=eng, n_shards=knobs["n_shards"],
        local_capacity=knobs["local_capacity"],
        halo_capacity=knobs["halo_capacity"],
        migrate_capacity=knobs["migrate_capacity"])


# ---------------------------------------------------------------------------
# Templates — a zero state with the checkpoint's structure/shapes/dtypes
# ---------------------------------------------------------------------------

def _template_state(cfg: EngineConfig,
                    behaviors: Sequence[Behavior]) -> EngineState:
    """Structural twin of ``Simulation.init_state`` output (values unused)."""
    pool = stage_pool(cfg.capacity, list(behaviors),
                      jnp.zeros((1, 3), jnp.float32), policy=cfg.dtypes)
    dspec = cfg.diffusion
    conc = jnp.zeros(dspec.dims, jnp.float32) if dspec else jnp.zeros((1, 1, 1))
    env = None
    if cfg.rebuild.mode == "every_k":
        env = grid_mod.initial_rebuild_state(
            cfg.grid_spec, cfg.capacity,
            jnp.asarray(cfg.domain_lo, jnp.float32),
            jnp.asarray(cfg.cell_size, jnp.float32))
    return EngineState(pool=pool, conc=conc, rng=jax.random.PRNGKey(0),
                       iteration=jnp.zeros((), jnp.int32),
                       stats=StepStats.zeros(), env=env)


def _template_dist_state(dcfg: DistConfig,
                         behaviors: Sequence[Behavior]) -> DistState:
    """Structural twin of ``DistributedSimulation.init_state`` output."""
    cfg = dcfg.engine
    staging = stage_pool(1, list(behaviors), jnp.zeros((1, 3), jnp.float32),
                         extra_specs={OWNED: ((), jnp.bool_, True)},
                         policy=cfg.dtypes)
    n = dcfg.n_shards * dcfg.local_capacity
    channels = {k: jnp.zeros((n,) + v.shape[1:], v.dtype)
                for k, v in staging.channels().items()}
    dspec = cfg.diffusion
    conc = (jnp.zeros(dspec.dims, jnp.float32) if dspec
            else jnp.zeros((dcfg.n_shards, 1, 1)))
    env = None
    if cfg.rebuild.mode == "every_k":
        env0 = grid_mod.initial_rebuild_state(
            cfg.grid_spec, dcfg.total_capacity,
            jnp.asarray(cfg.domain_lo, jnp.float32),
            jnp.asarray(cfg.cell_size, jnp.float32))
        env = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (dcfg.n_shards,)
                                       + a.shape).copy(), env0)
    return DistState(channels=channels, conc=conc,
                     rng=jnp.zeros((dcfg.n_shards, 2), jnp.uint32),
                     boundaries=jnp.zeros((dcfg.n_shards + 1,), jnp.float32),
                     iteration=jnp.zeros((), jnp.int32),
                     stats=StepStats.zeros((dcfg.n_shards,)), env=env)


def _adapt_env(state, saved_mode: str, cfg: EngineConfig, template_fn):
    """Reconcile env presence when the target rebuild mode differs from the
    checkpoint's (a supervisor may have degraded every_k → every_step)."""
    if (cfg.rebuild.mode == "every_k") == (saved_mode == "every_k"):
        return state
    if cfg.rebuild.mode == "every_step":
        return dataclasses.replace(state, env=None)
    # target wants a cache the checkpoint lacks: start from a dirty initial
    # cache — the first step rebuilds, which is always correct
    return dataclasses.replace(state, env=template_fn().env)


# ---------------------------------------------------------------------------
# Single-device save / restore
# ---------------------------------------------------------------------------

def save_state(ckpt_dir: str, state: EngineState, cfg: EngineConfig,
               extras: Optional[Dict] = None) -> str:
    """Atomic checkpoint of a complete single-device run state."""
    meta = {"format": _FORMAT, "kind": "engine", "knobs": _engine_knobs(cfg)}
    if extras:
        meta.update(extras)
    return ckpt_mod.save(ckpt_dir, int(state.iteration), state, extras=meta)


def restore_state(ckpt_dir: str, cfg: EngineConfig,
                  behaviors: Sequence[Behavior], step: Optional[int] = None,
                  apply_knobs: str = "all"
                  ) -> Tuple[EngineState, EngineConfig]:
    """Restore ``(state, config)``; resume by building Simulation(config).

    ``step=None`` restores the latest checkpoint. ``apply_knobs`` decides
    which recorded knobs overwrite ``cfg`` (see ``_apply_engine_knobs``) —
    with "all", stepping the returned state under the returned config is
    bit-exact with the uninterrupted run.
    """
    if step is None:
        step = ckpt_mod.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    meta = ckpt_mod.load_manifest(ckpt_dir, step).get("extras", {})
    knobs = meta.get("knobs")
    if knobs is None:
        raise ValueError(f"{ckpt_dir} step {step}: not a simulation "
                         f"checkpoint (no knobs in manifest extras)")
    cfg = _apply_engine_knobs(cfg, knobs, apply_knobs)
    saved_mode = knobs["rebuild"]["mode"]
    # the restore template mirrors the config the checkpoint was SAVED
    # under (env presence / grid shapes), then adapts to the target config
    tmpl_cfg = cfg
    if (cfg.rebuild.mode == "every_k") != (saved_mode == "every_k"):
        tmpl_cfg = dataclasses.replace(
            cfg, rebuild=grid_mod.RebuildPolicy(**knobs["rebuild"]))
    state = ckpt_mod.restore(ckpt_dir, step,
                             _template_state(tmpl_cfg, behaviors))
    state = _adapt_env(state, saved_mode, cfg,
                       lambda: _template_state(cfg, behaviors))
    return state, cfg


# ---------------------------------------------------------------------------
# Ensemble save / restore
# ---------------------------------------------------------------------------

def save_ensemble_state(ckpt_dir: str, state: EnsembleState,
                        cfg: EngineConfig,
                        extras: Optional[Dict] = None) -> str:
    """Atomic checkpoint of a whole ensemble — every lane's state, the
    active mask, per-lane params, and the tick, as one pytree. The step
    index is the ensemble ``tick`` (per-lane iterations travel as arrays).
    Callers with host-side lane bookkeeping (serve/sim_service.py's request
    table) record it through ``extras``."""
    meta = {"format": _FORMAT, "kind": "ensemble",
            "knobs": _engine_knobs(cfg), "n_lanes": state.n_lanes}
    if extras:
        meta.update(extras)
    return ckpt_mod.save(ckpt_dir, int(state.tick), state, extras=meta)


def restore_ensemble_state(ckpt_dir: str, cfg: EngineConfig,
                           behaviors: Sequence[Behavior],
                           params_template: Optional[ScenarioParams] = None,
                           step: Optional[int] = None,
                           apply_knobs: str = "all"
                           ) -> Tuple[EnsembleState, EngineConfig, Dict]:
    """Restore ``(state, config, manifest_extras)`` for an ensemble run.

    Same bit-exactness contract as :func:`restore_state`: with
    ``apply_knobs="all"`` the restored config rebuilds the exact jit program
    the checkpoint ran under, so stepping the restored ensemble replays the
    uninterrupted trajectory byte for byte on every lane.
    ``params_template`` must match the structure the run was saved with
    (the restore template is built from it). The returned extras dict gives
    services their lane table back.
    """
    if step is None:
        step = ckpt_mod.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    meta = ckpt_mod.load_manifest(ckpt_dir, step).get("extras", {})
    knobs = meta.get("knobs")
    if knobs is None or meta.get("kind") != "ensemble":
        raise ValueError(f"{ckpt_dir} step {step}: not an ensemble "
                         f"simulation checkpoint")
    cfg = _apply_engine_knobs(cfg, knobs, apply_knobs)
    n_lanes = meta["n_lanes"]
    saved_mode = knobs["rebuild"]["mode"]
    tmpl_cfg = cfg
    if (cfg.rebuild.mode == "every_k") != (saved_mode == "every_k"):
        tmpl_cfg = dataclasses.replace(
            cfg, rebuild=grid_mod.RebuildPolicy(**knobs["rebuild"]))
    tmpl = EnsembleEngine(tmpl_cfg, behaviors, n_lanes,
                          params_template).init_state()
    state = ckpt_mod.restore(ckpt_dir, step, tmpl)
    state = _adapt_env(
        state, saved_mode, cfg,
        lambda: EnsembleEngine(cfg, behaviors, n_lanes,
                               params_template).init_state())
    return state, cfg, meta


# ---------------------------------------------------------------------------
# Distributed save / restore
# ---------------------------------------------------------------------------

def save_dist_state(ckpt_dir: str, state: DistState, dcfg: DistConfig,
                    extras: Optional[Dict] = None) -> str:
    """Atomic checkpoint of a distributed run (all shards' slabs at once:
    the channel arrays are already the global sharded buffers)."""
    meta = {"format": _FORMAT, "kind": "dist", "knobs": _dist_knobs(dcfg)}
    if extras:
        meta.update(extras)
    return ckpt_mod.save(ckpt_dir, int(state.iteration), state, extras=meta)


def restore_dist_state(ckpt_dir: str, dcfg: DistConfig,
                       behaviors: Sequence[Behavior],
                       step: Optional[int] = None, apply_knobs: str = "all",
                       seed: int = 0) -> Tuple[DistState, DistConfig]:
    """Restore ``(state, dist_config)`` — elastic across shard counts.

    Same ``n_shards`` as the checkpoint: exact restore (bit-exact resume;
    a larger ``local_capacity`` rung in ``dcfg`` re-packs slabs through the
    ladder's own restage). Different ``n_shards``: live agents are gathered
    and re-partitioned through the init path (fresh quantile boundaries,
    fresh per-shard RNG folded from ``seed``) — a valid state with the same
    population, but a different layout/stream, so not bit-exact.
    """
    if step is None:
        step = ckpt_mod.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    meta = ckpt_mod.load_manifest(ckpt_dir, step).get("extras", {})
    knobs = meta.get("knobs")
    if knobs is None or meta.get("kind") != "dist":
        raise ValueError(f"{ckpt_dir} step {step}: not a distributed "
                         f"simulation checkpoint")
    saved_mode = knobs["engine"]["rebuild"]["mode"]
    if dcfg.n_shards == knobs["n_shards"]:
        target = _apply_dist_knobs(dcfg, knobs, apply_knobs)
        grow_local = max(dcfg.local_capacity, target.local_capacity)
        tmpl_cfg = target
        if (target.engine.rebuild.mode == "every_k") != (saved_mode
                                                         == "every_k"):
            tmpl_cfg = dataclasses.replace(
                target, engine=dataclasses.replace(
                    target.engine, rebuild=grid_mod.RebuildPolicy(
                        **knobs["engine"]["rebuild"])))
        state = ckpt_mod.restore(ckpt_dir, step,
                                 _template_dist_state(tmpl_cfg, behaviors))
        state = _adapt_env(state, saved_mode, target.engine,
                           lambda: _template_dist_state(target, behaviors))
        if grow_local > target.local_capacity:
            # caller's rung outgrew the checkpoint's: repack, keep the rung
            state = dataclasses.replace(state, channels=compaction.repack_slabs(
                state.channels, target.n_shards, target.local_capacity,
                grow_local))
            target = dataclasses.replace(target, local_capacity=grow_local)
        return state, target

    # --- reshard: restore at the saved topology, re-partition live agents
    saved_dcfg = _apply_dist_knobs(dcfg, knobs, "all")
    tmpl = _template_dist_state(saved_dcfg, behaviors)
    state = ckpt_mod.restore(ckpt_dir, step, tmpl)
    target = dcfg if apply_knobs == "rungs" else dataclasses.replace(
        dcfg, engine=_apply_engine_knobs(dcfg.engine, knobs["engine"], "all"))
    cfg = target.engine
    ch = {k: jnp.asarray(np.asarray(v)) for k, v in state.channels.items()}
    boundaries = quantile_boundaries(ch["position"][:, 0], ch["alive"],
                                     target.n_shards,
                                     float(cfg.domain_lo[0]),
                                     float(cfg.domain_hi[0]))
    n_live = int(np.asarray(ch["alive"]).sum())
    channels = partition_global(ch, boundaries, target)
    kept = int(np.asarray(channels["alive"]).sum())
    if kept != n_live:
        raise ValueError(
            f"reshard onto n_shards={target.n_shards} drops "
            f"{n_live - kept} agents (a slab exceeds local_capacity="
            f"{target.local_capacity}); raise local_capacity")
    dspec = cfg.diffusion
    conc = (state.conc if dspec
            else jnp.zeros((target.n_shards, 1, 1)))
    rng = jax.vmap(lambda s: jax.random.fold_in(
        jax.random.PRNGKey(seed), s))(
            jnp.arange(target.n_shards, dtype=jnp.uint32))
    env = _template_dist_state(target, behaviors).env   # dirty: rebuilds
    return DistState(channels=channels, conc=conc, rng=rng,
                     boundaries=boundaries, iteration=state.iteration,
                     stats=StepStats.zeros((target.n_shards,)),
                     env=env), target


class SimCheckpointer:
    """Async simulation checkpointer: snapshot-to-host, background write.

    One object per run; saves are serialized (a new save waits for the
    previous write). Dispatches on state type, records the knobs alongside.
    """

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self._async = ckpt_mod.AsyncCheckpointer(ckpt_dir, keep=keep)

    def save_async(self, state, config, extras: Optional[Dict] = None) -> int:
        step = int(state.iteration)
        if isinstance(config, DistConfig):
            meta = {"format": _FORMAT, "kind": "dist",
                    "knobs": _dist_knobs(config)}
        else:
            meta = {"format": _FORMAT, "kind": "engine",
                    "knobs": _engine_knobs(config)}
        if extras:
            meta.update(extras)
        self._async.save_async(step, state, extras=meta)
        return step

    def wait(self) -> None:
        self._async.wait()


# ---------------------------------------------------------------------------
# Degradation policy + run report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DegradationPolicy:
    """Ordered remedies the supervisor tries after a rollback.

    The order is by trajectory impact: (1) drop the every_k rebuild cache —
    stale-superset candidates contribute exactly zero force, so positions
    are unchanged and only the skip schedule resets; (2) drop the
    fused/Pallas sweep to the sequential XLA path — bit-exact by
    construction (tests/test_fused.py); (3) shrink dt — the only remedy
    that changes the trajectory, tried last and at most
    ``max_dt_shrinks`` times.
    """

    dt_shrink: float = 0.5
    max_dt_shrinks: int = 2

    def next_remedy(self, cfg: EngineConfig, applied: Sequence[str]
                    ) -> Optional[Tuple[str, EngineConfig]]:
        """(name, degraded config) — or None when out of remedies."""
        if cfg.rebuild.mode == "every_k":
            return "rebuild_every_step", dataclasses.replace(
                cfg, rebuild=grid_mod.RebuildPolicy())
        if cfg.fused_sweep or cfg.force_impl != "xla":
            return "sequential_sweep", dataclasses.replace(
                cfg, fused_sweep=False, force_impl="xla")
        if sum(1 for a in applied if a == "shrink_dt") < self.max_dt_shrinks:
            return "shrink_dt", dataclasses.replace(
                cfg, dt=cfg.dt * self.dt_shrink)
        return None


@dataclasses.dataclass
class RunReport:
    """Structured record of everything the supervisor did to keep the run
    alive — the contract is that no intervention is silent."""

    interventions: List[Dict] = dataclasses.field(default_factory=list)
    checkpoints: List[int] = dataclasses.field(default_factory=list)
    rungs: List[Dict] = dataclasses.field(default_factory=list)
    retries: int = 0
    completed: bool = False
    final_iteration: int = 0

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# The supervised run loop
# ---------------------------------------------------------------------------

class SupervisedRunner:
    """Fault-tolerant driver around a capacity ladder (§7.5).

    Wraps a ``CapacityLadder`` (or ``DistributedCapacityLadder``): runs it
    step by step, checkpoints every ``checkpoint_every`` iterations (plus
    once up front, so there is always a rollback target), and reads the
    in-graph health bitmask after every step. On a health fault or
    ``CapacityExhausted``:

      1. the failing state is discarded (for capacity exhaustion, the
         last-good pre-step state carried by the exception is first
         emergency-checkpointed — no progress is lost);
      2. the engine config is degraded one remedy down the
         ``DegradationPolicy`` ladder;
      3. the run rolls back to the latest checkpoint (rung knobs from the
         checkpoint, degraded knobs kept) and continues.

    When remedies run out the original fault is re-raised with the
    ``RunReport`` attached — the trajectory up to the last checkpoint is on
    disk either way.

    ``fault_hook(iteration, state) -> state | None`` is a test-only
    injection point, called on the *input* state of each iteration, so
    injected corruption flows through the jitted step and is caught by the
    in-graph guard exactly like real corruption would be.
    """

    def __init__(self, driver, ckpt_dir: str, checkpoint_every: int = 50,
                 keep: int = 3, policy: Optional[DegradationPolicy] = None,
                 max_retries: int = 8,
                 fault_hook: Optional[Callable] = None):
        self.driver = driver
        self.ckpt_dir = ckpt_dir
        self.checkpoint_every = checkpoint_every
        self.policy = policy or DegradationPolicy()
        self.max_retries = max_retries
        self.fault_hook = fault_hook
        self.report = RunReport()
        self._ckpt = SimCheckpointer(ckpt_dir, keep=keep)
        self._applied: List[str] = []

    # -- driver plumbing (CapacityLadder vs DistributedCapacityLadder) ------
    def _is_dist(self) -> bool:
        return isinstance(self.driver, DistributedCapacityLadder)

    def _config(self):
        return self.driver.dcfg if self._is_dist() else self.driver.config

    def _engine_cfg(self) -> EngineConfig:
        c = self._config()
        return c.engine if self._is_dist() else c

    def _reconfigure(self, new_cfg) -> None:
        if self._is_dist():
            self.driver.dcfg = new_cfg
            self.driver._sim = DistributedSimulation(
                new_cfg, self.driver.behaviors, self.driver._mesh,
                self.driver.axis)
        else:
            self.driver.config = new_cfg
            self.driver._sim = Simulation(new_cfg, self.driver.behaviors)

    def _save(self, state) -> None:
        step = self._ckpt.save_async(state, self._config())
        if step not in self.report.checkpoints:
            self.report.checkpoints.append(step)

    def _rollback(self):
        """Latest checkpoint under the current (possibly degraded) config."""
        self._ckpt.wait()
        if self._is_dist():
            state, cfg = restore_dist_state(
                self.ckpt_dir, self._config(), self.driver.behaviors,
                apply_knobs="rungs")
        else:
            state, cfg = restore_state(
                self.ckpt_dir, self._config(), self.driver.behaviors,
                apply_knobs="rungs")
        self._reconfigure(cfg)
        return state

    def _handle_fault(self, kind: str, detail: Dict, fault) -> Any:
        self.report.retries += 1
        if self.report.retries > self.max_retries:
            fault.report = self.report
            raise fault
        remedy = self.policy.next_remedy(self._engine_cfg(), self._applied)
        if remedy is None:
            fault.report = self.report
            raise fault
        name, new_eng = remedy
        self._applied.append(name)
        new_cfg = (dataclasses.replace(self._config(), engine=new_eng)
                   if self._is_dist() else new_eng)
        self._reconfigure(new_cfg)
        state = self._rollback()
        self.report.interventions.append(
            {"kind": kind, "remedy": name,
             "rolled_back_to": int(state.iteration), **detail})
        return state

    # -- the loop -----------------------------------------------------------
    def run(self, state, n_iterations: int):
        """Returns ``(final_state, RunReport)``."""
        target = int(state.iteration) + n_iterations
        self._save(state)                       # always a rollback target
        while int(state.iteration) < target:
            it = int(state.iteration)
            if self.fault_hook is not None:
                injected = self.fault_hook(it, state)
                if injected is not None:
                    state = injected
            try:
                nxt = self.driver.step(state)
                bits = nxt.stats.health_bits()
                if bits:
                    raise HealthFault(
                        f"iteration {it}: health guard fired "
                        f"{describe(bits)}", bits=bits)
            except HealthFault as e:
                state = self._handle_fault(
                    "health", {"iteration": it, "flags": list(e.flags)}, e)
                continue
            except CapacityExhausted as e:
                if e.state is not None:
                    # emergency checkpoint of the last-good pre-step state:
                    # rollback loses nothing
                    self._ckpt.wait()
                    self._save(e.state)
                state = self._handle_fault(
                    "capacity_exhausted",
                    {"iteration": it, "demand": e.demand,
                     "max_capacity": e.max_capacity}, e)
                continue
            state = nxt
            if int(state.iteration) % self.checkpoint_every == 0:
                self._save(state)
        self._save(state)
        self._ckpt.wait()
        self.report.completed = True
        self.report.final_iteration = int(state.iteration)
        self.report.rungs = list(self.driver.rungs)
        return state, self.report
