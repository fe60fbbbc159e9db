"""NeuriteGrowth and Lineage through the engine's step.

Forces are off and no behavior moves the agents it does not name, so each
expected position follows from the behavior's own arithmetic. The grid
build permutes the pool every step (slot ids are stable only within an
iteration), so agents are matched by position or label, never by slot.
"""

import numpy as np

from repro.core import EngineConfig, Simulation
from repro.core.behaviors import (GROWTH_CONE, NEURITE_SEGMENT, GrowDivide,
                                  Lineage, NeuriteGrowth)


def _cfg(capacity: int) -> EngineConfig:
    return EngineConfig(capacity=capacity, domain_lo=(0.0,) * 3,
                        domain_hi=(100.0,) * 3, interaction_radius=12.0,
                        dt=1.0, use_forces=False, max_per_box=64)


def _cones(n: int):
    rng = np.random.default_rng(3)
    pos = rng.uniform(30.0, 70.0, (n, 3)).astype(np.float32)
    d = rng.standard_normal((n, 3)).astype(np.float32)
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    return pos, d


def _live(st):
    n = int(st.stats["n_live"])
    pool = st.pool
    return (n, np.asarray(pool.position)[:n], np.asarray(pool.agent_type)[:n],
            {k: np.asarray(v)[:n] for k, v in pool.extra.items()})


def _rows(a):
    return a[np.lexsort(a.T[::-1])]


def test_neurite_deposits_a_segment_when_path_len_crosses_segment_every():
    n = 12
    pos, d = _cones(n)
    sim = Simulation(_cfg(64), [NeuriteGrowth(speed=1.0, noise=0.0,
                                              bifurcation_prob=0.0,
                                              segment_every=2.0)])
    st = sim.init_state(pos, diameter=np.full(n, 2.0, np.float32),
                        agent_type=np.full(n, GROWTH_CONE, np.int32),
                        extra_init={"direction": d})
    st = sim.step(st)                      # path_len 1 < 2: no segment yet
    n1, p1, t1, x1 = _live(st)
    assert n1 == n and (t1 == GROWTH_CONE).all()
    np.testing.assert_allclose(x1["path_len"], 1.0)
    st = sim.step(st)                      # path_len reaches 2: deposit
    n2, p2, t2, x2 = _live(st)
    assert n2 == 2 * n
    seg, cone = t2 == NEURITE_SEGMENT, t2 == GROWTH_CONE
    assert seg.sum() == n and cone.sum() == n
    # each segment stays where its cone stood before this step
    np.testing.assert_allclose(_rows(p2[seg]), _rows(p1), atol=1e-5)
    np.testing.assert_allclose(_rows(p2[cone]), _rows(pos + 2.0 * d),
                               atol=1e-5)
    np.testing.assert_array_equal(x2["path_len"], 0.0)
    np.testing.assert_array_equal(x2["direction"][seg], 0.0)


def test_neurite_bifurcation_prob_one_stages_a_second_cone_for_every_cone():
    n = 12
    pos, d = _cones(n)
    sim = Simulation(_cfg(64), [NeuriteGrowth(speed=1.0, noise=0.0,
                                              bifurcation_prob=1.0,
                                              segment_every=100.0)])
    st = sim.init_state(pos, diameter=np.full(n, 2.0, np.float32),
                        agent_type=np.full(n, GROWTH_CONE, np.int32),
                        extra_init={"direction": d})
    n1, p1, t1, x1 = _live(sim.step(st))
    assert n1 == 2 * n and (t1 == GROWTH_CONE).all()
    # both cones of a pair stand at the mother's new position ...
    np.testing.assert_allclose(_rows(p1)[0::2], _rows(pos + d), atol=1e-5)
    np.testing.assert_allclose(_rows(p1)[1::2], _rows(pos + d), atol=1e-5)
    # ... and the second one leaves along a rotated unit direction
    np.testing.assert_allclose(np.linalg.norm(x1["direction"], axis=1), 1.0,
                               atol=1e-5)
    kept = np.isclose(x1["direction"][:, None, :], d[None], atol=1e-5)
    assert kept.all(-1).any(-1).sum() == n


def test_lineage_label_follows_the_daughter_through_the_permutation():
    n = 32
    rng = np.random.default_rng(5)
    pos = rng.uniform(10.0, 90.0, (n, 3)).astype(np.float32)
    sim = Simulation(_cfg(128), [Lineage(),
                                 GrowDivide(rate=0.0, threshold_diameter=8.0)])
    st = sim.init_state(pos, diameter=np.full(n, 8.0, np.float32))
    st = sim.run(st, 2)           # every seed divides once, then one rebuild
    m, p, _, x = _live(st)
    assert m == 2 * n
    clone = x["clone"]
    assert not np.array_equal(clone[:n], np.arange(1, n + 1)), \
        "the grid build left the pool in seed order: nothing was permuted"
    radius = 8.0 * 0.5 ** (1.0 / 3.0) * 0.5
    for label in range(1, n + 1):
        rows = np.flatnonzero(clone == label)
        assert len(rows) == 2, (label, rows)
        dist = np.linalg.norm(p[rows] - pos[label - 1], axis=1)
        # the mother stays on her seed; the daughter sits one radius away
        np.testing.assert_allclose(np.sort(dist), [0.0, radius], atol=1e-5)
