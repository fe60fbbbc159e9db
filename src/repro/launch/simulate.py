"""ABM simulation driver: named scenarios from the paper's Table 1, CLI-sized.

    PYTHONPATH=src python -m repro.launch.simulate --scenario proliferation \
        --agents 10000 --iterations 100 [--force-impl pallas]

Fault-tolerant mode (DESIGN.md §7.5): ``--supervised --ckpt-dir DIR`` runs
under the checkpointing supervisor — periodic atomic checkpoints, in-graph
health guards, rollback + degradation on faults, and a structured run report
printed at the end. ``--resume`` continues a killed run from the latest
checkpoint in ``--ckpt-dir`` (bit-exact with the uninterrupted run).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from ..core import (CapacityLadder, EngineConfig, ForceParams, Simulation,
                    SupervisedRunner, restore_state)
from ..core.behaviors import (Chemotaxis, GrowDivide, Infection, NeuriteGrowth,
                              RandomDeath, RandomWalk, Secretion,
                              GROWTH_CONE, INFECTED)
from ..core.diffusion import DiffusionSpec
from . import compile_cache

SCENARIOS = ("proliferation", "clustering", "epidemiology", "neuroscience",
             "oncology")


def build(scenario: str, n: int, force_impl: str):
    rng = np.random.default_rng(0)
    if scenario == "proliferation":
        side = max(120.0, (n ** (1 / 3)) * 14)
        cfg = EngineConfig(capacity=max(4 * n, 1024), domain_lo=(0,) * 3,
                           domain_hi=(side,) * 3, interaction_radius=14.0,
                           dt=0.2, sort_frequency=10, max_per_box=128,
                           force_impl=force_impl,
                           force=ForceParams(max_displacement=1.0))
        sim = Simulation(cfg, [GrowDivide(rate=0.6, threshold_diameter=12.0)])
        pos = rng.uniform(side * 0.4, side * 0.6, (n, 3)).astype(np.float32)
        st = sim.init_state(pos, diameter=np.full(n, 8.0, np.float32))
    elif scenario == "clustering":
        side = max(64.0, (n ** (1 / 3)) * 4)
        dim = int(side // 2)
        cfg = EngineConfig(capacity=n, domain_lo=(0,) * 3,
                           domain_hi=(side,) * 3, interaction_radius=3.0,
                           use_forces=False, query_chunk=4096,
                           diffusion=DiffusionSpec(dims=(dim,) * 3,
                                                   coefficient=0.5,
                                                   decay=0.01, voxel=2.0))
        sim = Simulation(cfg, [Secretion(rate=2.0), Chemotaxis(speed=0.35)])
        pos = rng.uniform(4, side - 4, (n, 3)).astype(np.float32)
        st = sim.init_state(pos, diameter=np.full(n, 1.0, np.float32))
    elif scenario == "epidemiology":
        side = max(100.0, (n ** (1 / 3)) * 5)
        cfg = EngineConfig(capacity=n, domain_lo=(0,) * 3,
                           domain_hi=(side,) * 3, interaction_radius=3.0,
                           use_forces=False, query_chunk=4096)
        sim = Simulation(cfg, [RandomWalk(sigma=0.8),
                               Infection(radius=3.0, beta=0.25,
                                         recovery_time=40)])
        pos = rng.uniform(0, side, (n, 3)).astype(np.float32)
        types = np.zeros(n, np.int32)
        types[:max(n // 1000, 5)] = INFECTED
        st = sim.init_state(pos, diameter=np.full(n, 1.0, np.float32),
                            agent_type=types,
                            extra_init={"infect_timer":
                                        np.full(n, 40, np.int32)})
    elif scenario == "neuroscience":
        cfg = EngineConfig(capacity=max(40 * n, 2048), domain_lo=(0,) * 3,
                           domain_hi=(160,) * 3, interaction_radius=4.0,
                           dt=0.5, detect_static=True, sort_frequency=20,
                           max_per_box=64, force_impl=force_impl,
                           force=ForceParams(max_displacement=0.2,
                                             move_eps=1e-4))
        sim = Simulation(cfg, [NeuriteGrowth(speed=0.8, noise=0.2,
                                             bifurcation_prob=0.008)])
        pos = rng.uniform(70, 90, (n, 3)).astype(np.float32)
        d0 = rng.standard_normal((n, 3)).astype(np.float32)
        d0 /= np.linalg.norm(d0, axis=1, keepdims=True)
        st = sim.init_state(pos, diameter=np.full(n, 2.0, np.float32),
                            agent_type=np.full(n, GROWTH_CONE, np.int32),
                            extra_init={"direction": d0})
    elif scenario == "oncology":
        side = max(160.0, (n ** (1 / 3)) * 16)
        cfg = EngineConfig(capacity=max(8 * n, 2048), domain_lo=(0,) * 3,
                           domain_hi=(side,) * 3, interaction_radius=14.0,
                           dt=0.2, sort_frequency=10, max_per_box=160,
                           force_impl=force_impl,
                           force=ForceParams(max_displacement=1.0))
        sim = Simulation(cfg, [GrowDivide(rate=0.7, threshold_diameter=12.0),
                               RandomWalk(sigma=0.1),
                               RandomDeath(rate=0.012)])
        pos = rng.uniform(side * 0.35, side * 0.65, (n, 3)).astype(np.float32)
        st = sim.init_state(pos, diameter=np.full(n, 9.0, np.float32))
    else:
        raise SystemExit(f"unknown scenario {scenario}")
    return sim, st


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", choices=SCENARIOS, default="proliferation")
    ap.add_argument("--agents", type=int, default=10_000)
    ap.add_argument("--iterations", type=int, default=100)
    ap.add_argument("--force-impl", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--report-every", type=int, default=20)
    ap.add_argument("--supervised", action="store_true",
                    help="run under the fault-tolerant supervisor (§7.5)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (required with --supervised)")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir")
    args = ap.parse_args()
    compile_cache.enable()

    sim, st = build(args.scenario, args.agents, args.force_impl)
    if args.supervised or args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--supervised/--resume require --ckpt-dir")
        cfg, behaviors = sim.config, sim.behaviors
        if args.resume:
            st, cfg = restore_state(args.ckpt_dir, cfg, behaviors)
            print(f"resumed from {args.ckpt_dir} at iteration "
                  f"{int(st.iteration)}")
        runner = SupervisedRunner(CapacityLadder(cfg, behaviors),
                                  args.ckpt_dir,
                                  checkpoint_every=args.checkpoint_every)
        t0 = time.time()
        st, report = runner.run(st, args.iterations)
        dt = time.time() - t0
        print(f"iter {int(st.iteration):5d}  "
              f"n_live={int(st.stats['n_live']):8d}  "
              f"{args.iterations / dt:6.2f} iter/s")
        print("run report: " + json.dumps(report.to_dict()))
        print("done")
        return

    t0 = time.time()
    done = 0
    while done < args.iterations:
        k = min(args.report_every, args.iterations - done)
        st = sim.run(st, k, check_overflow=True)
        done += k
        dt = time.time() - t0
        print(f"iter {done:5d}  n_live={int(st.stats['n_live']):8d}  "
              f"n_active={int(st.stats['n_active']):8d}  "
              f"{done / dt:6.2f} iter/s  "
              f"{int(st.stats['n_live']) * done / dt:,.0f} agent·iter/s")
    print("done")


if __name__ == "__main__":
    main()
