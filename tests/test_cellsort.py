"""Cell sorting by differential adhesion (paper Fig 7 / §6.5).

Biocellion's cell-sorting model (§3.1 there): two cell types, same-type
adhesion stronger than cross-type, start as a random mixture and
segregate. The physics check: the fraction of same-type neighbours within
the interaction radius rises by more than 0.02 over 45 steps, on the XLA
force path and on the Pallas K1 kernel (interpret mode on a CPU). The
fraction is counted by plain NumPy over all pairs, independent of the
engine's grid.
"""

import numpy as np
import pytest

from repro.core import EngineConfig, ForceParams, Simulation

N = 1000
RADIUS = 4.5
ADHESION = ((0.30, 0.06), (0.06, 0.30))     # same-type >> cross-type
SIDE = 40.0            # seeds fill [10, 30]^3: 0.125 cells per unit volume


def _same_type_fraction(st) -> float:
    alive = np.asarray(st.pool.alive)
    pos = np.asarray(st.pool.position)[alive].astype(np.float64)
    typ = np.asarray(st.pool.agent_type)[alive]
    n = len(pos)
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    near = (d2 <= RADIUS * RADIUS) & ~np.eye(n, dtype=bool)
    same = near & (typ[:, None] == typ[None, :])
    return same.sum() / max(near.sum(), 1)


@pytest.mark.parametrize("force_impl", ["xla", "pallas"])
def test_differential_adhesion_segregates_types(force_impl):
    rng = np.random.default_rng(7)
    cfg = EngineConfig(capacity=N, domain_lo=(0, 0, 0), domain_hi=(SIDE,) * 3,
                       interaction_radius=RADIUS, dt=0.1, sort_frequency=10,
                       adhesion=ADHESION, max_per_box=64,
                       force_impl=force_impl,
                       force=ForceParams(k_rep=1.5, adhesion_band=0.8,
                                         max_displacement=0.4))
    sim = Simulation(cfg, [])
    pos = rng.uniform(10.0, SIDE - 10.0, (N, 3)).astype(np.float32)
    types = rng.integers(0, 2, N).astype(np.int32)
    st = sim.init_state(pos, diameter=np.full(N, 3.2, np.float32),
                        agent_type=types)
    f0 = _same_type_fraction(st)
    st = sim.run(st, 45, check_overflow=True)
    f1 = _same_type_fraction(st)
    assert int(st.stats["n_live"]) == N
    assert f1 > f0 + 0.02, f"same-type fraction {f0:.3f} -> {f1:.3f}"
