"""SimService (DESIGN.md §8): continuous batching over ensemble lanes.

The serving corner cases the batching loop must get right: admission into
a full pool queues (never drops), retirement frees the lane at iteration
granularity and the next request reuses it with a fresh RNG stream, an
all-idle service never launches the jitted step, and a checkpoint taken
mid-churn resumes bit-exact.
"""

import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import EngineConfig, ScenarioParams
from repro.core import behaviors as bhv
from repro.serve import SimRequest, SimService

N = 96


def _cfg():
    return EngineConfig(
        capacity=128, domain_lo=(0.0,) * 3, domain_hi=(48.0,) * 3,
        interaction_radius=3.0, use_forces=False, detect_static=False,
        query_chunk=1024, max_per_box=32)


def _behaviors():
    return [bhv.RandomWalk(sigma=0.8),
            bhv.Infection(radius=3.0, beta=lambda ctx: ctx.params["beta"],
                          recovery_time=30)]


def _req(uid, seed, beta, max_steps=40):
    r = np.random.RandomState(seed)
    pos = r.uniform(0, 48, (N, 3)).astype(np.float32)
    at = np.zeros((N,), np.int32)
    at[:8] = bhv.INFECTED
    timer = np.zeros((N,), np.int32)
    timer[:8] = 30
    return SimRequest(uid=uid, position=pos,
                      diameter=np.full((N,), 1.0, np.float32), agent_type=at,
                      extra_init={"infect_timer": timer}, seed=seed,
                      params=ScenarioParams.of(beta=beta),
                      max_steps=max_steps)


def _metrics(pool, params):
    return jnp.sum((pool.agent_type == bhv.INFECTED) & pool.alive)


def _service(n_lanes=3):
    return SimService(_cfg(), _behaviors(), n_lanes=n_lanes,
                      params_template=ScenarioParams.of(beta=0.0),
                      metrics_fn=_metrics,
                      converged_fn=lambda m: int(m) == 0)


def test_full_pool_queues_never_drops():
    svc = _service(n_lanes=3)
    for u in range(6):
        svc.submit(_req(u, seed=100 + u, beta=0.2, max_steps=12))
    assert len(svc.queue) == 6
    # first tick admits exactly n_lanes; the overflow stays queued
    assert svc.step() == 3
    assert len(svc.queue) == 3
    assert svc.occupancy() == 1.0
    ticks = svc.run_until_drained()
    # every request ran to completion — none dropped, all retired
    assert len(svc.finished) == 6
    assert sorted(f.uid for f in svc.finished) == list(range(6))
    assert all(f.reason in ("converged", "max_steps") for f in svc.finished)
    assert all(len(f.trajectory) == f.steps for f in svc.finished)
    # 6 budget-12 sims over 3 lanes cannot drain faster than two waves
    # (ticks counts from after the one manual step above)
    assert 1 + ticks >= 24


def test_all_idle_early_exit_skips_jit():
    svc = _service(n_lanes=2)
    assert svc.step() == 0                       # nothing queued, all idle
    assert int(svc.state.tick) == 0              # jitted step never launched
    svc.submit(_req(0, seed=5, beta=0.2, max_steps=3))
    svc.run_until_drained()
    tick_after = int(svc.state.tick)
    assert svc.step() == 0                       # drained → idle again
    assert int(svc.state.tick) == tick_after


def test_lane_reuse_has_independent_rng_stream():
    """A request admitted into a recycled lane must produce exactly what it
    would have produced in a fresh service — the previous occupant's rng
    stream, params, and state leave nothing behind."""
    churned = _service(n_lanes=1)
    churned.submit(_req(0, seed=7, beta=0.3, max_steps=9))    # occupant 1
    churned.submit(_req(1, seed=21, beta=0.45, max_steps=11))  # reuses lane 0
    churned.run_until_drained()
    assert [f.uid for f in churned.finished] == [0, 1]
    reused = next(f for f in churned.finished if f.uid == 1)

    fresh = _service(n_lanes=1)
    fresh.submit(_req(1, seed=21, beta=0.45, max_steps=11))
    fresh.run_until_drained()
    alone = fresh.finished[0]

    assert reused.steps == alone.steps and reused.reason == alone.reason
    for name, av in reused.final.pool.channels().items():
        assert np.array_equal(np.asarray(av),
                              np.asarray(alone.final.pool.channels()[name])), \
            f"reused-lane channel {name} diverged from fresh-service run"
    assert np.array_equal(np.asarray(reused.final.rng),
                          np.asarray(alone.final.rng))
    assert [int(np.asarray(m)) for m in reused.trajectory] == \
           [int(np.asarray(m)) for m in alone.trajectory]


def test_checkpoint_resume_bit_exact_mid_churn():
    svc = _service(n_lanes=3)
    for u in range(5):
        svc.submit(_req(10 + u, seed=200 + u, beta=0.2 + 0.05 * u,
                        max_steps=8))
    for _ in range(10):
        svc.step()          # mid-churn: some retired, lanes reused
    assert svc.finished and any(i is not None for i in svc.lanes)

    with tempfile.TemporaryDirectory() as d:
        finished_at_ckpt = sorted(f.uid for f in svc.finished)
        svc.checkpoint(d, extras={"finished_uids": finished_at_ckpt})
        table_at_ckpt = [None if i is None else i["req"].uid
                         for i in svc.lanes]
        for _ in range(6):
            svc.step()      # original continues

        svc2 = _service(n_lanes=3)
        tick = svc2.restore(d)
        assert tick == int(svc2.state.tick)
        assert svc2.restored_meta["finished_uids"] == finished_at_ckpt
        # lane table restored: same uids busy as at checkpoint time
        busy = [None if i is None else i["req"].uid for i in svc2.lanes]
        assert busy == table_at_ckpt
        for _ in range(6):
            svc2.step()     # replay the same 6 ticks

        eq = jax.tree_util.tree_map(
            lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
            svc.state.pool.channels(), svc2.state.pool.channels())
        assert all(eq.values()), \
            [k for k, v in eq.items() if not v]
        assert np.array_equal(np.asarray(svc.state.rng),
                              np.asarray(svc2.state.rng))
        assert np.array_equal(np.asarray(svc.state.active),
                              np.asarray(svc2.state.active))
        assert np.array_equal(np.asarray(svc.state.iteration),
                              np.asarray(svc2.state.iteration))
