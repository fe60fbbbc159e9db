#!/usr/bin/env python3
"""Bring-up smoke run of the simulation engine on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the distributed engine only

One process drives every phase; it starts no child process. With one chip:

  1. device   -- JAX must see a TPU; any other platform fails the run.
  2. oncology -- the oncology deployment of ``launch/simulate.py``
                 (forces, GrowDivide, RandomWalk, RandomDeath; 1,048,576
                 seed agents at capacity 8,388,608) stepped through
                 ``Simulation.run(check_overflow=True)``: one step on the
                 XLA force path (the reference), then from the same initial
                 state three steps on the Pallas K1 path. After one step
                 ``n_live`` must be identical and live positions within
                 ``POS_TOL``; the Pallas step's compiled text must hold
                 ``tpu_custom_call`` (native kernel, not interpret mode).
  3. exact    -- ``uniform_grid`` against the O(N^2) ``brute_force``
                 environment at 4,096 agents for 5 steps.
  4. service  -- 4 requests of 32,768 agents through a 4-lane ``SimService``
                 for 20 steps; all must drain, and one request's final
                 state must equal a solo ``Simulation`` run bit for bit.

With ``--chips 4`` only this runs: ``DistributedSimulation`` over 4 chips
with 1,048,576 agents per chip against a single-device ``Simulation`` of the
same population for 3 steps, in deterministic behaviors (fixed drift,
Infection with beta=1.0). Integers must match exactly, floats to 1e-5
relative.

Each phase prints one ``phase <name> {json}`` line with its wall time,
compile time and the device's ``peak_bytes_in_use``; these are observations
of one run, not benchmark results. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed. Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (DistConfig, DistributedSimulation,  # noqa: E402
                        EngineConfig, ForceParams, Simulation, telemetry)
from repro.core.behaviors import (INFECTED, Behavior,  # noqa: E402
                                  BehaviorEffects, Infection, RandomWalk)
from repro.launch import compile_cache, sim_serve, simulate  # noqa: E402

POS_TOL = 1e-3      # µm: max |Δposition| between the XLA and K1 force paths
                    # after one step (f32 ulp at the deployment's ~1,600 µm
                    # domain edge is 1.2e-4; the force sums differ only in
                    # reduction order)
EXACT_TOL = 1e-5    # rtol = atol of grid vs brute force (tests/test_engine.py)
DIST_RTOL = 1e-5    # 4-shard vs single-device floats


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes() -> int | None:
    """Largest ``peak_bytes_in_use`` over the local devices."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    return max((p for p in peaks if p is not None), default=None)


def ready(state):
    return jax.block_until_ready(state)


def timed(fn, *args, **kwargs):
    """Run ``fn`` to completion; return (result, wall seconds, seconds of
    that spent tracing, lowering and compiling)."""
    c0, t0 = telemetry.compile_seconds(), time.perf_counter()
    out = ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0, telemetry.compile_seconds() - c0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_devices(want: int) -> dict:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {len(devices)} {d0.platform} "
                           f"device(s) ({d0.device_kind})")
    check(len(devices) >= want,
          f"need {want} TPU chips, found {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def phase_oncology(n_agents: int = 1 << 20, k1_steps: int = 3) -> dict:
    """XLA force path for one step (the reference), then the K1 path from
    the same initial state for ``k1_steps`` steps."""
    sim, st0 = simulate.build("oncology", n_agents, "xla")
    sim_k1 = Simulation(dataclasses.replace(sim.config, force_impl="pallas"),
                        sim.behaviors)
    st0 = ready(st0)

    st1, t_xla, c_xla = timed(sim.run, st0, 1, check_overflow=True)
    k1_1, t_k1, c_k1 = timed(sim_k1.run, st0, 1, check_overflow=True)
    k1_n, t_k1_rest, _ = timed(sim_k1.run, k1_1, k1_steps - 1,
                               check_overflow=True)
    k1_text = sim_k1._step_fn.lower(st0).compile().as_text()

    alive = np.asarray(st1.pool.alive)
    alive_k1 = np.asarray(k1_1.pool.alive)
    same_alive = bool(np.array_equal(alive, alive_k1))
    pos_err = (float(np.abs(np.asarray(st1.pool.position)[alive]
                            - np.asarray(k1_1.pool.position)[alive]).max())
               if same_alive else None)
    out = {"seed_agents": n_agents, "capacity": sim.config.capacity,
           "xla_step1_s": t_xla, "xla_step1_compile_s": c_xla,
           "xla_n_live": int(st1.stats.n_live),
           "k1_step1_s": t_k1, "k1_step1_compile_s": c_k1,
           "k1_step_s": t_k1_rest / max(k1_steps - 1, 1),
           "k1_n_live": [int(k1_1.stats.n_live), int(k1_n.stats.n_live)],
           "max_pos_err": pos_err,
           "tpu_custom_call": "tpu_custom_call" in k1_text}
    print_phase("oncology", out)
    check(out["xla_n_live"] == out["k1_n_live"][0],
          f"n_live after step 1: xla {out['xla_n_live']} "
          f"vs k1 {out['k1_n_live'][0]}")
    check(same_alive, "alive masks differ between XLA and K1 after step 1")
    check(pos_err <= POS_TOL, f"positions differ by {pos_err} > {POS_TOL}")
    check(out["tpu_custom_call"],
          "the K1 step holds no tpu_custom_call: the kernel ran in "
          "interpret mode")
    return out


def phase_exact(n_agents: int = 4096, steps: int = 5) -> dict:
    rng = np.random.default_rng(0)
    pos = rng.uniform(10, 90, (n_agents, 3)).astype(np.float32)
    dia = np.full(n_agents, 5.0, np.float32)
    finals, n_live, pairs = {}, {}, 0
    for env in ("uniform_grid", "brute_force"):
        cfg = EngineConfig(capacity=n_agents, domain_lo=(0.0,) * 3,
                           domain_hi=(100.0,) * 3, interaction_radius=6.0,
                           dt=0.1, environment=env, max_per_box=64,
                           force=ForceParams(max_displacement=0.5))
        sim = Simulation(cfg, [])
        st = sim.run(sim.init_state(pos, diameter=dia), steps,
                     check_overflow=True)
        finals[env] = np.asarray(st.pool.position)
        n_live[env] = int(st.stats.n_live)
        pairs = int(np.asarray(st.pool.force_nnz).sum())
    err = np.abs(finals["uniform_grid"] - finals["brute_force"])
    out = {"agents": n_agents, "steps": steps, "n_live": n_live,
           "max_pos_err": float(err.max()), "interacting_pairs": pairs}
    print_phase("exact", out)
    check(n_live["uniform_grid"] == n_live["brute_force"] == n_agents,
          f"n_live {n_live}")
    check(pairs > 0, "no agent felt a force: the comparison saw nothing")
    check(bool(np.all(err <= EXACT_TOL
                      + EXACT_TOL * np.abs(finals["brute_force"]))),
          f"grid vs brute force: max |Δposition| {err.max()}")
    return out


def phase_service(lanes: int = 4, agents: int = 32768,
                  steps: int = 20) -> dict:
    side = max(40.0, (agents ** (1 / 3)) * 5)
    recovery = 40
    betas = np.linspace(0.1, 0.5, lanes)
    svc = sim_serve.make_service(lanes, agents, side)
    cfg = svc.driver.config
    for uid in range(lanes):
        svc.submit(sim_serve.make_request(uid, agents, side, float(betas[uid]),
                                          recovery, steps))
    t0 = time.perf_counter()
    ticks = svc.run_until_drained()
    t_serve = time.perf_counter() - t0

    done = {f.uid: f for f in svc.finished}
    probe = done.get(0)
    mismatched = ["<request 0 did not finish>"]
    if probe is not None:
        req = sim_serve.make_request(0, agents, side, float(betas[0]),
                                     recovery, steps)
        solo = Simulation(cfg, [RandomWalk(sigma=0.8),
                                Infection(radius=3.0, beta=float(betas[0]),
                                          recovery_time=recovery)])
        st = solo.init_state(req.position, req.diameter, req.agent_type,
                             req.extra_init, seed=req.seed)
        st = solo.run(st, probe.steps, check_overflow=True)
        mine, theirs = probe.final.pool.channels(), st.pool.channels()
        mismatched = [k for k in mine
                      if not np.array_equal(np.asarray(mine[k]),
                                            np.asarray(theirs[k]))]
        if not np.array_equal(np.asarray(probe.final.rng),
                              np.asarray(st.rng)):
            mismatched.append("rng")
    out = {"lanes": lanes, "agents": agents, "requests": lanes,
           "ticks": ticks, "serve_s": t_serve,
           "finished": sorted(done), "solo_mismatch": mismatched}
    print_phase("service", out)
    check(sorted(done) == list(range(lanes)) and not svc.queue,
          f"service did not drain: finished {sorted(done)}")
    check(not mismatched,
          f"request 0 differs from its solo run in {mismatched}")
    return out


class TaggedDrift(Behavior):
    """Deterministic +x drift that carries an int32 ``tag`` identity channel,
    so the sharded and single-device populations match agent by agent."""

    def __init__(self, vx: float):
        self.vx = vx

    def extra_specs(self):
        return {"tag": ((), jnp.int32, -1)}

    def __call__(self, ctx, pool, rng):
        step = jnp.asarray([self.vx, 0.0, 0.0]) * ctx.dt
        new_pos = jnp.where(ctx.owned[:, None], pool.position + step,
                            pool.position)
        new_pos = jnp.clip(new_pos, ctx.domain_lo, ctx.domain_hi)
        return BehaviorEffects(set_channels={"position": new_pos})


DIST_RADIUS = 4.0


def dist_setup(n: int):
    """Engine config and population of the four-chip comparison.

    One agent per box on average (box edge = radius), so a 3-box z-run
    holds 3: ``max_per_run=24`` keeps the gathered stream narrow, and
    ``check_overflow`` fails the run if any run or box overflows."""
    side = float(np.ceil(n ** (1 / 3)) * DIST_RADIUS)
    rng = np.random.default_rng(0)
    pos = rng.uniform(1.0, side - 1.0, (n, 3)).astype(np.float32)
    dia = np.full(n, 2.0, np.float32)
    types = np.zeros(n, np.int32)
    types[rng.choice(n, max(n // 1000, 8), replace=False)] = INFECTED
    extra = {"infect_timer": np.full(n, 3, np.int32),
             "tag": np.arange(n, dtype=np.int32)}
    cfg = EngineConfig(capacity=n, domain_lo=(0.0,) * 3,
                       domain_hi=(side,) * 3, interaction_radius=DIST_RADIUS,
                       dt=0.5, max_per_box=16, max_per_run=24,
                       query_chunk=4096,
                       force=ForceParams(max_displacement=0.5))
    return cfg, (pos, dia, types, extra)


def dist_behaviors():
    return [TaggedDrift(1.2),
            Infection(radius=DIST_RADIUS, beta=1.0, recovery_time=3)]


def phase_distributed(n_shards: int = 4, per_shard: int = 1 << 20,
                      steps: int = 3) -> dict:
    n = n_shards * per_shard
    cfg, population = dist_setup(n)
    sim = Simulation(cfg, dist_behaviors())
    st0 = ready(sim.init_state(*population))
    st, t_single, c_single = timed(sim.run, st0, steps, check_overflow=True)
    del st0

    # ghost band at uniform density: n·r/side agents per face; ×2 headroom
    band = int(n * DIST_RADIUS / cfg.domain_hi[0] * 2) + 1024
    dcfg = DistConfig(engine=cfg, n_shards=n_shards,
                      local_capacity=int(per_shard * 1.25),
                      halo_capacity=band,
                      migrate_capacity=max(4096, band // 2))
    dsim = DistributedSimulation(dcfg, dist_behaviors())
    dst0 = ready(dsim.init_state(*population))
    dst, t_dist, c_dist = timed(dsim.run, dst0, steps, check_overflow=True)

    mesh_ids = sorted(d.id for d in dsim.mesh.devices.ravel())
    shard_ids = sorted({s.device.id for s
                        in dst.channels["position"].addressable_shards})

    def by_tag(ch, alive):
        tag = np.asarray(ch["extra.tag"])[alive]
        order = np.argsort(tag)
        return {k: np.asarray(v)[alive][order] for k, v in ch.items()}

    ref = by_tag(st.pool.channels(), np.asarray(st.pool.alive))
    out_ch = {k: v for k, v in dsim.gather_channels(dst).items()
              if k in ref}
    got = by_tag(out_ch, out_ch["alive"])
    same_n = len(ref["extra.tag"]) == len(got["extra.tag"])
    int_mismatch, float_err = [], {}
    if same_n:
        for k, a in ref.items():
            b = got[k]
            if np.issubdtype(a.dtype, np.floating):
                float_err[k] = float((np.abs(a - b)
                                      / np.maximum(np.abs(a), 1.0)).max())
            elif not np.array_equal(a, b):
                int_mismatch.append(k)
    infected = int((ref["agent_type"] != 0).sum())
    out = {"shards": n_shards, "agents": n, "agents_per_shard": per_shard,
           "steps": steps, "mesh_devices": mesh_ids,
           "shard_devices": shard_ids,
           "single_device_s": t_single, "single_compile_s": c_single,
           "distributed_s": t_dist, "distributed_compile_s": c_dist,
           "n_live": [len(ref["extra.tag"]), len(got["extra.tag"])],
           "n_live_per_shard": np.asarray(dst.stats.n_live).ravel().tolist(),
           "infected_or_recovered": infected,
           "int_mismatch": int_mismatch, "float_rel_err": float_err}
    print_phase("distributed", out)
    check(len(set(shard_ids)) == n_shards == len(mesh_ids),
          f"state sits on devices {shard_ids}, mesh {mesh_ids}")
    check(same_n, f"live agents: single {len(ref['extra.tag'])} vs "
                  f"distributed {len(got['extra.tag'])}")
    check(bool(np.array_equal(got["extra.tag"], np.arange(n))),
          "agent identities were lost or duplicated")
    check(not int_mismatch, f"integer channels differ: {int_mismatch}")
    check(all(e <= DIST_RTOL for e in float_err.values()),
          f"float channels differ: {float_err}")
    check(infected > n // 1000, "the infection never spread")
    return out


# ---------------------------------------------------------------------------

def print_phase(name: str, out: dict) -> None:
    print(f"phase {name} " + json.dumps(out), flush=True)


def run_phase(name: str, fn) -> None:
    c0, t0 = telemetry.compile_seconds(), time.perf_counter()
    fn()
    print(f"phase {name} done " + json.dumps({
        "wall_s": time.perf_counter() - t0,
        "compile_s": telemetry.compile_seconds() - c0,
        "peak_bytes_in_use": peak_bytes()}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        device = check_devices(args.chips)
        print("device " + json.dumps(device), flush=True)
        print(f"compile cache: {compile_cache.enable()}", flush=True)
        if args.chips == 4:
            run_phase("distributed", phase_distributed)
        else:
            run_phase("oncology", phase_oncology)
            run_phase("exact", phase_exact)
            run_phase("service", phase_service)
    except Exception:  # noqa: BLE001 — report every failure, exit non-zero
        traceback.print_exc()
        print("FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
