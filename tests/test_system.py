"""End-to-end behaviour test for the paper's system.

The headline claim, executed small: a full simulation with every paper
optimization enabled runs, conserves invariants, and skips static work.
(Checkpoint/resume bit-exactness: tests/test_fault_tolerance.py and
tests/test_launch.py.)"""

import numpy as np

from repro.core import EngineConfig, ForceParams, Simulation
from repro.core.behaviors import GrowDivide, RandomDeath


def test_full_engine_all_optimizations(rng):
    """Paper Fig 9 configuration: optimized grid + Morton sorting + static
    detection + parallel add/remove, all at once, on a churning population."""
    cfg = EngineConfig(capacity=2048, domain_lo=(0, 0, 0),
                       domain_hi=(120, 120, 120), interaction_radius=12.0,
                       dt=0.2, sort_frequency=5, detect_static=True,
                       max_per_box=128,
                       force=ForceParams(max_displacement=1.0))
    sim = Simulation(cfg, [GrowDivide(rate=0.8, threshold_diameter=12.0),
                           RandomDeath(rate=0.01)])
    pos = rng.uniform(40, 80, (128, 3)).astype(np.float32)
    st = sim.init_state(pos, diameter=np.full(128, 8.0, np.float32))
    st = sim.run(st, 40, check_overflow=True)
    n = int(st.stats["n_live"])
    alive = np.asarray(st.pool.alive)
    assert n > 0
    assert alive[:n].all() and not alive[n:].any()       # compaction invariant
    assert not np.isnan(np.asarray(st.pool.position)).any()
    assert int(st.stats["n_active"]) <= n                # statics never exceed

