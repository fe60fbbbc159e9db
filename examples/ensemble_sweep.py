"""SIR parameter sweep on the ensemble engine (DESIGN.md §8).

One vmapped iteration core advances every sweep member in lockstep: N lanes,
each a small SIR world with its own (beta, gamma) drawn from a grid, served
through the continuous-batching SimService — more parameter points than
lanes, so lanes retire and re-admit as members finish. Prints the aggregate
epidemic-size surface over the (beta, gamma) grid.

    PYTHONPATH=src python examples/ensemble_sweep.py

Environment knobs (CI smoke caps size):
    EXAMPLE_N       agents per lane        (default 400)
    EXAMPLE_LANES   ensemble lanes         (default 8)
    EXAMPLE_POINTS  sweep points           (default 16)
    EXAMPLE_STEPS   per-member step budget (default 120)
"""

import os

import numpy as np
import jax.numpy as jnp

from repro.core import EngineConfig, ScenarioParams
from repro.core.behaviors import (INFECTED, Infection, RandomWalk,
                                  SUSCEPTIBLE)
from repro.serve import SimRequest, SimService

N_AGENTS = int(os.environ.get("EXAMPLE_N", 400))
N_LANES = int(os.environ.get("EXAMPLE_LANES", 8))
N_POINTS = int(os.environ.get("EXAMPLE_POINTS", 16))
MAX_STEPS = int(os.environ.get("EXAMPLE_STEPS", 120))
SIDE = max(30.0, (N_AGENTS ** (1 / 3)) * 4.2)


def make_service() -> SimService:
    # sweep regime: comparison sort — the counting sort's scatter passes
    # batch poorly under the lane axis on XLA:CPU (ROADMAP D2)
    cfg = EngineConfig(capacity=-(-N_AGENTS // 64) * 64,
                       domain_lo=(0, 0, 0), domain_hi=(SIDE,) * 3,
                       interaction_radius=3.0, use_forces=False,
                       query_chunk=2048, max_per_box=32,
                       sort_impl="argsort")
    behaviors = [
        RandomWalk(sigma=0.8),
        # per-lane rates flow through ScenarioParams → ctx.params: one
        # compiled program serves every (beta, gamma) point
        Infection(radius=3.0, beta=lambda ctx: ctx.params["beta"],
                  recovery_time=lambda ctx: ctx.params["recovery_time"]),
    ]

    def infected(pool, params):
        return jnp.sum((pool.agent_type == INFECTED) & pool.alive)

    return SimService(cfg, behaviors, n_lanes=N_LANES,
                      params_template=ScenarioParams.of(beta=0.0,
                                                        recovery_time=1),
                      metrics_fn=infected,
                      converged_fn=lambda m: int(m) == 0)


def make_request(uid: int, beta: float, recovery_time: int) -> SimRequest:
    r = np.random.RandomState(7000 + uid)
    pos = r.uniform(0, SIDE, (N_AGENTS, 3)).astype(np.float32)
    types = np.zeros(N_AGENTS, np.int32)
    n0 = max(N_AGENTS // 50, 2)
    types[:n0] = INFECTED
    timer = np.zeros(N_AGENTS, np.int32)
    timer[:n0] = recovery_time
    return SimRequest(uid=uid, position=pos,
                      diameter=np.full(N_AGENTS, 1.0, np.float32),
                      agent_type=types,
                      extra_init={"infect_timer": timer}, seed=uid,
                      params=ScenarioParams.of(beta=beta,
                                               recovery_time=recovery_time),
                      max_steps=MAX_STEPS)


def main():
    # (beta, gamma) grid: gamma realized as integer recovery_time = 1/gamma
    n_beta = max(int(np.sqrt(N_POINTS)), 2)
    n_rec = -(-N_POINTS // n_beta)
    betas = np.linspace(0.1, 0.6, n_beta)
    recoveries = np.unique(np.linspace(10, 60, n_rec).astype(int))
    points = [(float(b), int(rt)) for rt in recoveries for b in betas]

    svc = make_service()
    for uid, (beta, rt) in enumerate(points):
        svc.submit(make_request(uid, beta, rt))
    print(f"sweep: {len(points)} members ({n_beta} beta × {len(recoveries)} "
          f"recovery), {N_LANES} lanes, {N_AGENTS} agents/lane")

    ticks = svc.run_until_drained()
    assert len(svc.finished) == len(points)

    print(f"drained in {ticks} ticks "
          f"(vs {sum(f.steps for f in svc.finished)} sequential steps)")
    print(f"{'beta':>6} {'1/gamma':>8} {'steps':>6} {'reason':>10} "
          f"{'peak_I':>7} {'attack_rate':>12}")
    attack = {}
    for f in sorted(svc.finished, key=lambda f: f.uid):
        beta, rt = points[f.uid]
        t = np.asarray(f.final.pool.agent_type)[np.asarray(f.final.pool.alive)]
        rate = float((t != SUSCEPTIBLE).sum()) / max(len(t), 1)
        peak = max(int(np.asarray(m)) for m in f.trajectory)
        attack[(beta, rt)] = rate
        print(f"{beta:6.2f} {rt:8d} {f.steps:6d} {f.reason:>10} "
              f"{peak:7d} {rate:12.3f}")

    # aggregate trajectory sanity: infectivity must matter — the most
    # aggressive corner of the sweep infects more than the mildest
    lo = attack[(float(betas[0]), int(recoveries[0]))]
    hi = attack[(float(betas[-1]), int(recoveries[-1]))]
    assert hi >= lo, f"attack rate not increasing with (beta, 1/gamma): " \
                     f"{lo:.3f} -> {hi:.3f}"
    assert hi > 0, "no epidemic anywhere in the sweep"
    print(f"OK: attack rate {lo:.3f} (mild corner) -> {hi:.3f} "
          f"(aggressive corner) over {len(points)} members")


if __name__ == "__main__":
    main()
