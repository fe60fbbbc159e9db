"""Fault-tolerance subsystem (DESIGN.md §7.5): health guards, checkpoint /
resume, rollback-with-degradation, and crash recovery.

Every recovery path is exercised deterministically through the test-only
fault-injection hooks in core/health.py (NaN writes, bit flips, flag storms)
plus real SIGKILLs delivered to subprocess runs:

  * in-graph health bitmask: each predicate fires on exactly its fault;
  * checkpoint round-trips are bit-exact, every_k cache included;
  * CapacityExhausted carries the last-good state (supervisors recover);
  * the SupervisedRunner survives an injected NaN — rollback + degradation
    recorded in the run report, final state bit-exact with a clean run
    (the fused→sequential remedy is bit-exact, so recovery is invisible);
  * a SIGKILLed single-device capacity-ladder run resumes from the latest
    checkpoint bit-exact vs an uninterrupted oracle (subprocess);
  * same for a 4-shard distributed run, which also restores onto a
    different shard count (subprocess, conftest keeps this process 1-CPU).
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CapacityExhausted, CapacityLadder, EngineConfig,
                        ForceParams, LadderConfig, Simulation,
                        SupervisedRunner, restore_state, save_state)
from repro.core import health, simcheck
from repro.core.behaviors import GrowDivide, RandomWalk
from repro.core.grid import RebuildPolicy
from repro.core.stats import StepStats


def _cfg(**kw):
    base = dict(capacity=64, domain_lo=(0, 0, 0), domain_hi=(32, 32, 32),
                interaction_radius=2.0, dt=0.1, max_per_box=32,
                query_chunk=64, force=ForceParams(max_displacement=0.5))
    base.update(kw)
    return EngineConfig(**base)


def _pos(n=20, seed=0):
    return np.random.default_rng(seed).uniform(2, 30, (n, 3)).astype(
        np.float32)


def _same_trees(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# health predicates
# ---------------------------------------------------------------------------

def test_step_health_unit_bits():
    hcfg = health.HealthConfig(max_step_displacement=1.0)
    mask = jnp.asarray([True, True, False])
    lo = jnp.zeros(3)
    hi = jnp.full(3, 10.0)
    good = jnp.full((3, 3), 5.0)
    move = jnp.zeros((3, 3))
    assert int(health.step_health(hcfg, mask, good, lo, hi,
                                  move_d=move)) == 0
    nanp = good.at[0, 1].set(jnp.nan)
    assert int(health.step_health(hcfg, mask, nanp, lo, hi,
                                  move_d=move)) == health.NONFINITE
    esc = good.at[1, 2].set(11.0)
    assert int(health.step_health(hcfg, mask, esc, lo, hi,
                                  move_d=move)) == health.ESCAPE
    jump = move.at[0, 0].set(2.0)
    assert int(health.step_health(hcfg, mask, good, lo, hi,
                                  move_d=jump)) == health.DISPLACEMENT
    # masked rows never report (ghost/dead slots)
    dead_nan = good.at[2, 0].set(jnp.nan)
    assert int(health.step_health(hcfg, mask, dead_nan, lo, hi,
                                  move_d=move)) == 0
    # NaN force under the finite check
    nf = jnp.zeros((3, 3)).at[1, 0].set(jnp.inf)
    assert int(health.step_health(hcfg, mask, good, lo, hi, force=nf,
                                  move_d=move)) == health.NONFINITE
    assert health.describe(health.NONFINITE | health.ESCAPE) == (
        "nonfinite", "domain_escape")


def test_engine_detects_injected_nan_in_graph():
    sim = Simulation(_cfg(), [])
    st = sim.run(sim.init_state(_pos()), 2)
    assert int(st.stats["health"]) == 0
    bad = health.inject_value(st, "position", 3, np.nan)
    out = sim.step(bad)
    assert out.stats.health_bits() & health.NONFINITE
    # observability only: nothing raised, the run continued
    assert int(out.stats["n_live"]) > 0


def test_engine_detects_escape_and_flip_bits():
    sim = Simulation(_cfg(use_forces=False), [])
    st = sim.run(sim.init_state(_pos()), 1)
    esc = health.inject_value(st, "position", 5, 99.0)   # outside the box
    out = sim.step(esc)
    assert out.stats.health_bits() & health.ESCAPE
    # a flipped sign bit throws the agent below domain_lo deterministically
    flip = health.flip_bits(st, "position", 2, mask=0x80000000)
    out2 = sim.step(flip)
    assert out2.stats.health_bits() & health.ESCAPE


def test_engine_displacement_guard():
    hcfg = health.HealthConfig(max_step_displacement=0.05)
    sim = Simulation(_cfg(use_forces=False, health=hcfg),
                     [RandomWalk(sigma=5.0)])
    st = sim.step(sim.init_state(_pos()))
    assert st.stats.health_bits() & health.DISPLACEMENT


def test_health_disabled_entirely():
    sim = Simulation(_cfg(health=None), [])
    st = sim.step(sim.init_state(_pos()))
    assert int(st.stats["health"]) == 0


def test_storm_flags_injection():
    sim = Simulation(_cfg(), [])
    st = sim.step(sim.init_state(_pos()))
    stormy = health.storm_flags(st, "birth_overflow", 3)
    assert stormy.stats.flags() == {"birth_overflow": 3}
    assert stormy.stats.any_overflow()


# ---------------------------------------------------------------------------
# StepStats helpers
# ---------------------------------------------------------------------------

def test_stats_flags_helpers():
    s = StepStats.zeros()
    assert s.flags() == {} and not s.any_overflow() and s.health_bits() == 0
    s = dataclasses.replace(s, halo_overflow=jnp.asarray(2, jnp.int32),
                            box_demand=jnp.asarray(99, jnp.int32),
                            health=jnp.asarray(5, jnp.int32))
    assert s.flags() == {"halo_overflow": 2}      # demands are not flags
    assert s.any_overflow()
    assert s.health_bits() == 5
    # per-shard vectors reduce across shards
    v = StepStats.zeros((4,))
    v = dataclasses.replace(
        v, birth_overflow=jnp.asarray([0, 1, 0, 2], jnp.int32),
        health=jnp.asarray([1, 0, 4, 0], jnp.int32))
    assert v.flags() == {"birth_overflow": 3}
    assert v.health_bits() == 5


# ---------------------------------------------------------------------------
# CapacityExhausted
# ---------------------------------------------------------------------------

def test_capacity_exhausted_carries_state():
    cfg = _cfg(capacity=32, domain_hi=(64, 64, 64), interaction_radius=6.0,
               max_per_box=64, dt=0.2,
               force=ForceParams(max_displacement=1.0))
    lad = CapacityLadder(cfg, [GrowDivide(rate=3.0, threshold_diameter=5.0)],
                         LadderConfig(max_capacity=48))
    # diameter 3.0 → ~4 growth steps before the mass division, so the
    # carried last-good state is a real mid-run state, not the init state
    st = lad.init_state(np.random.default_rng(1).uniform(
        20, 44, (30, 3)).astype(np.float32),
        diameter=np.full(30, 3.0, np.float32))
    with pytest.raises(CapacityExhausted, match="ladder exhausted") as e:
        lad.run(st, 60)
    exc = e.value
    assert isinstance(exc, RuntimeError)          # legacy contract
    assert exc.state is not None and exc.stats is not None
    assert exc.iteration == int(exc.state.iteration)
    assert exc.demand > exc.max_capacity == 48
    # the carried state is steppable — a supervisor can checkpoint it
    assert int(exc.state.stats["n_live"]) > 0


# ---------------------------------------------------------------------------
# checkpoint / resume (single device, in-process)
# ---------------------------------------------------------------------------

def test_simcheck_roundtrip_bit_exact(tmp_path):
    cfg = _cfg()
    sim = Simulation(cfg, [RandomWalk(sigma=0.2)])
    st = sim.run(sim.init_state(_pos(), seed=7), 5)
    save_state(str(tmp_path), st, cfg)
    st2, cfg2 = restore_state(str(tmp_path), cfg, [RandomWalk(sigma=0.2)])
    assert _same_trees(st, st2)
    a = sim.run(st, 6)
    b = Simulation(cfg2, [RandomWalk(sigma=0.2)]).run(st2, 6)
    assert _same_trees(a, b), "resume must be bit-exact"


def test_simcheck_roundtrip_every_k_cache(tmp_path):
    cfg = _cfg(rebuild=RebuildPolicy(mode="every_k", k=4,
                                     displacement_bound=0.5))
    sim = Simulation(cfg, [RandomWalk(sigma=0.05)])
    st = sim.run(sim.init_state(_pos(), seed=3), 6)
    save_state(str(tmp_path), st, cfg)
    st2, cfg2 = restore_state(str(tmp_path), cfg, [RandomWalk(sigma=0.05)])
    assert st2.env is not None
    assert int(st2.env.steps_since) == int(st.env.steps_since)
    a = sim.run(st, 7)
    b = Simulation(cfg2, [RandomWalk(sigma=0.05)]).run(st2, 7)
    assert _same_trees(a, b), \
        "every_k skip schedule must survive the round-trip bit-exactly"
    # rebuild accounting carried over: skip cadence identical
    assert int(a.stats["rebuilds"]) == int(b.stats["rebuilds"])


def test_restore_adapts_env_across_rebuild_modes(tmp_path):
    cfg = _cfg(rebuild=RebuildPolicy(mode="every_k", k=4,
                                     displacement_bound=0.5))
    sim = Simulation(cfg, [])
    st = sim.run(sim.init_state(_pos()), 3)
    save_state(str(tmp_path), st, cfg)
    # a degraded target config dropped the cache: env must be dropped too
    target = _cfg()        # every_step
    st2, cfg2 = restore_state(str(tmp_path), target, [], apply_knobs="rungs")
    assert cfg2.rebuild.mode == "every_step" and st2.env is None
    Simulation(cfg2, []).run(st2, 2)             # steppable


def test_restore_rejects_non_sim_checkpoint(tmp_path):
    from repro.core import checkpoint
    checkpoint.save(str(tmp_path), 1, {"a": jnp.ones(3)})
    with pytest.raises(ValueError, match="not a simulation checkpoint"):
        restore_state(str(tmp_path), _cfg(), [])


# ---------------------------------------------------------------------------
# degradation policy + supervised runner
# ---------------------------------------------------------------------------

def test_degradation_policy_order():
    pol = simcheck.DegradationPolicy(max_dt_shrinks=2)
    cfg = _cfg(rebuild=RebuildPolicy(mode="every_k", k=4,
                                     displacement_bound=0.5))
    applied = []
    names = []
    while True:
        r = pol.next_remedy(cfg, applied)
        if r is None:
            break
        name, cfg = r
        names.append(name)
        applied.append(name)
    assert names == ["rebuild_every_step", "sequential_sweep", "shrink_dt",
                     "shrink_dt"]
    assert cfg.rebuild.mode == "every_step"
    assert not cfg.fused_sweep and cfg.force_impl == "xla"
    assert abs(cfg.dt - 0.1 * 0.25) < 1e-9


def test_supervisor_nan_rollback_and_degradation(tmp_path):
    cfg = _cfg()
    pos = _pos()
    clean = CapacityLadder(cfg, [])
    oracle = clean.run(clean.init_state(pos, seed=7), 12)

    fired = []

    def hook(it, state):
        if it == 6 and not fired:
            fired.append(it)
            return health.inject_value(state, "position", 3, np.nan)
        return None

    lad = CapacityLadder(cfg, [])
    runner = SupervisedRunner(lad, str(tmp_path), checkpoint_every=5,
                              fault_hook=hook)
    final, report = runner.run(lad.init_state(pos, seed=7), 12)
    assert report.completed and report.final_iteration == 12
    assert report.retries == 1
    [iv] = report.interventions
    assert iv["kind"] == "health" and "nonfinite" in iv["flags"]
    assert iv["remedy"] == "sequential_sweep"     # fused → sequential XLA
    assert iv["rolled_back_to"] == 5
    # the sequential remedy is bit-exact, so recovery leaves no trace
    assert _same_trees(oracle.pool, final.pool)
    assert int(final.iteration) == int(oracle.iteration)


def test_supervisor_reraises_with_report_when_remedies_exhausted(tmp_path):
    cfg = _cfg(fused_sweep=False)                 # only dt shrinks remain

    def hook(it, state):                          # corrupt every attempt
        return health.inject_value(state, "position", 1, np.nan)

    lad = CapacityLadder(cfg, [])
    runner = SupervisedRunner(
        lad, str(tmp_path), checkpoint_every=5,
        policy=simcheck.DegradationPolicy(max_dt_shrinks=1), fault_hook=hook)
    with pytest.raises(health.HealthFault) as e:
        runner.run(lad.init_state(_pos(), seed=7), 12)
    rep = e.value.report
    assert rep is not None and not rep.completed
    assert [iv["remedy"] for iv in rep.interventions] == ["shrink_dt"]


def test_supervisor_capacity_exhaustion_emergency_checkpoint(tmp_path):
    cfg = _cfg(capacity=32, domain_hi=(64, 64, 64), interaction_radius=6.0,
               max_per_box=64, dt=0.2,
               force=ForceParams(max_displacement=1.0))
    lad = CapacityLadder(cfg, [GrowDivide(rate=3.0, threshold_diameter=5.0)],
                         LadderConfig(max_capacity=48))
    st = lad.init_state(np.random.default_rng(1).uniform(
        20, 44, (30, 3)).astype(np.float32),
        diameter=np.full(30, 3.0, np.float32))
    runner = SupervisedRunner(lad, str(tmp_path), checkpoint_every=50,
                              max_retries=2)
    with pytest.raises(CapacityExhausted) as e:
        runner.run(st, 60)
    rep = e.value.report
    assert rep.retries > 0
    assert any(iv["kind"] == "capacity_exhausted"
               for iv in rep.interventions)
    # the emergency checkpoint preserved the last-good trajectory on disk
    from repro.core import checkpoint
    assert checkpoint.latest_step(str(tmp_path)) is not None


# ---------------------------------------------------------------------------
# crash-resume: SIGKILL mid-flight, resume bit-exact (subprocess)
# ---------------------------------------------------------------------------

_CRASH_SCRIPT = textwrap.dedent("""
    import hashlib, os, signal, sys
    import numpy as np
    from repro.core import (CapacityLadder, EngineConfig, ForceParams,
                            LadderConfig, SupervisedRunner, restore_state)
    from repro.core.behaviors import GrowDivide, RandomDeath, RandomWalk

    mode, ckpt = sys.argv[1], sys.argv[2]
    TOTAL, KILL_AT = 40, 23

    def make():
        cfg = EngineConfig(capacity=256, domain_lo=(0, 0, 0),
                           domain_hi=(160, 160, 160),
                           interaction_radius=14.0, dt=0.2,
                           sort_frequency=10, max_per_box=160,
                           force=ForceParams(max_displacement=1.0))
        behs = [GrowDivide(rate=0.7, threshold_diameter=12.0),
                RandomWalk(sigma=0.1), RandomDeath(rate=0.012)]
        return cfg, behs

    def digest(state):
        a = np.asarray(state.pool.alive)
        p = np.asarray(state.pool.position)[a]
        p = p[np.lexsort(p.T)]
        return hashlib.sha256(p.tobytes()).hexdigest()

    rng = np.random.default_rng(3)
    pos = rng.uniform(55, 105, (200, 3)).astype(np.float32)
    dia = np.full(200, 9.0, np.float32)
    cfg, behs = make()

    if mode == "oracle":
        lad = CapacityLadder(cfg, behs)
        st = lad.run(lad.init_state(pos, diameter=dia), TOTAL)
        print("RESULT " + digest(st) + " " + str(int(st.iteration)))
    elif mode == "kill":
        def hook(it, state):
            if it == KILL_AT:
                os.kill(os.getpid(), signal.SIGKILL)   # no cleanup, no atexit
            return None
        lad = CapacityLadder(cfg, behs)
        runner = SupervisedRunner(lad, ckpt, checkpoint_every=5,
                                  fault_hook=hook)
        runner.run(lad.init_state(pos, diameter=dia), TOTAL)
        print("RESULT survived")                        # must never print
    elif mode == "resume":
        st, rcfg = restore_state(ckpt, cfg, behs)
        lad = CapacityLadder(rcfg, behs)
        runner = SupervisedRunner(lad, ckpt, checkpoint_every=5)
        st, report = runner.run(st, TOTAL - int(st.iteration))
        assert report.completed, report
        print("RESULT " + digest(st) + " " + str(int(st.iteration)))
""")


def _run_child(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", _CRASH_SCRIPT] + args,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _result_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1][len("RESULT "):]


def test_sigkill_ladder_run_resumes_bit_exact(tmp_path):
    ckpt = str(tmp_path / "ck")
    killed = _run_child(["kill", ckpt])
    assert killed.returncode == -signal.SIGKILL, \
        f"child exited {killed.returncode}: {killed.stderr[-2000:]}"
    assert "RESULT survived" not in killed.stdout
    from repro.core import checkpoint
    assert checkpoint.latest_step(ckpt) is not None, \
        "no checkpoint survived the kill"
    resumed = _result_line(_run_child(["resume", ckpt]))
    oracle = _result_line(_run_child(["oracle", str(tmp_path / "unused")]))
    assert resumed == oracle, \
        f"resumed {resumed} != uninterrupted {oracle}"


# ---------------------------------------------------------------------------
# distributed: checkpoint/SIGKILL-resume/reshard on 4 shards (subprocess)
# ---------------------------------------------------------------------------

_DIST_SCRIPT = textwrap.dedent("""
    import hashlib, os, signal, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    from repro.core import (DistConfig, DistributedCapacityLadder,
                            DistributedSimulation, EngineConfig, ForceParams,
                            SupervisedRunner, restore_dist_state,
                            save_dist_state)
    from repro.core import health
    from repro.core.behaviors import RandomWalk

    mode, ckpt = sys.argv[1], sys.argv[2]
    TOTAL, KILL_AT = 16, 10
    SIDE = 48.0

    def make(n_shards=4, local=256):
        cfg = EngineConfig(capacity=512, domain_lo=(0, 0, 0),
                           domain_hi=(SIDE,) * 3, interaction_radius=4.0,
                           dt=0.1, max_per_box=64, query_chunk=128,
                           force=ForceParams(max_displacement=0.5))
        return DistConfig(engine=cfg, n_shards=n_shards,
                          local_capacity=local, halo_capacity=128,
                          migrate_capacity=64), [RandomWalk(sigma=0.3)]

    def digest(state):
        a = np.asarray(state.channels["alive"])
        p = np.asarray(state.channels["position"])[a]
        p = p[np.lexsort(p.T)]
        return hashlib.sha256(p.tobytes()).hexdigest()

    rng = np.random.default_rng(0)
    pos = rng.uniform(2, SIDE - 2, (400, 3)).astype(np.float32)
    dia = np.full(400, 3.0, np.float32)
    dcfg, behs = make()

    if mode == "oracle":
        lad = DistributedCapacityLadder(dcfg, behs)
        st = lad.run(lad.init_state(pos, diameter=dia), TOTAL)
        print("RESULT " + digest(st) + " " + str(int(st.iteration)))
    elif mode == "kill":
        def hook(it, state):
            if it == KILL_AT:
                os.kill(os.getpid(), signal.SIGKILL)
            return None
        lad = DistributedCapacityLadder(dcfg, behs)
        runner = SupervisedRunner(lad, ckpt, checkpoint_every=4,
                                  fault_hook=hook)
        runner.run(lad.init_state(pos, diameter=dia), TOTAL)
        print("RESULT survived")
    elif mode == "resume":
        st, rcfg = restore_dist_state(ckpt, dcfg, behs)
        lad = DistributedCapacityLadder(rcfg, behs)
        runner = SupervisedRunner(lad, ckpt, checkpoint_every=4)
        st, report = runner.run(st, TOTAL - int(st.iteration))
        assert report.completed, report
        print("RESULT " + digest(st) + " " + str(int(st.iteration)))
    elif mode == "reshard":
        # restore a 4-shard checkpoint onto 2 shards: population and
        # iteration survive; the run continues (layout differs, so no
        # bit-exactness claim)
        dsim = DistributedSimulation(dcfg, behs)
        st = dsim.run(dsim.init_state(pos, diameter=dia), 5)
        save_dist_state(ckpt, st, dcfg)
        n_before = int(np.asarray(st.channels["alive"]).sum())
        d2, _ = make(n_shards=2, local=512)
        st2, rcfg = restore_dist_state(ckpt, d2, behs)
        assert rcfg.n_shards == 2
        assert int(st2.iteration) == 5
        n_after = int(np.asarray(st2.channels["alive"]).sum())
        assert n_after == n_before, (n_before, n_after)
        out = DistributedSimulation(rcfg, behs).run(st2, 3,
                                                    check_overflow=True)
        print("RESULT ok " + str(int(np.asarray(
            out.channels["alive"]).sum())))
    elif mode == "inject":
        # in-graph guard + supervisor recovery on the distributed engine
        fired = []
        def hook(it, state):
            if it == 6 and not fired:
                fired.append(it)
                return health.inject_value(state, "position", 3, np.nan)
            return None
        lad = DistributedCapacityLadder(dcfg, behs)
        runner = SupervisedRunner(lad, ckpt, checkpoint_every=4,
                                  fault_hook=hook)
        st, report = runner.run(lad.init_state(pos, diameter=dia), TOTAL)
        assert report.completed, report
        assert len(report.interventions) == 1, report.interventions
        assert report.interventions[0]["kind"] == "health"
        lad2 = DistributedCapacityLadder(*make())
        oracle = lad2.run(lad2.init_state(pos, diameter=dia), TOTAL)
        assert digest(st) == digest(oracle), "recovery must be invisible"
        print("RESULT ok " + report.interventions[0]["remedy"])
""")


def _run_dist_child(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", _DIST_SCRIPT] + args,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_dist_sigkill_resume_bit_exact(tmp_path):
    ckpt = str(tmp_path / "ck")
    killed = _run_dist_child(["kill", ckpt])
    assert killed.returncode == -signal.SIGKILL, \
        f"child exited {killed.returncode}: {killed.stderr[-2000:]}"
    resumed = _result_line(_run_dist_child(["resume", ckpt]))
    oracle = _result_line(_run_dist_child(["oracle",
                                           str(tmp_path / "unused")]))
    assert resumed == oracle, \
        f"resumed {resumed} != uninterrupted {oracle}"


def test_dist_restore_onto_different_shard_count(tmp_path):
    out = _result_line(_run_dist_child(["reshard", str(tmp_path / "ck")]))
    assert out.startswith("ok "), out


def test_dist_nan_injection_supervised_recovery(tmp_path):
    out = _result_line(_run_dist_child(["inject", str(tmp_path / "ck")]))
    assert out == "ok sequential_sweep", out
