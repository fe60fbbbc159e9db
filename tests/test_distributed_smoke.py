"""Distributed never-silent smoke: SIR over 1, 2 and 4 host shards.

The weak-scaling points hold 2,000 agents per shard (1, 2 and 4 shards);
the strong-scaling points split 8,000 agents over 2 and 4 shards. Each run
is the shard_map engine with contact forces, a random walk and SIR
infection, quantile rebalance every 4 steps, and capacities sized as a
deployment would size them: local slabs at 1.25x the even share, a ghost
band from the face area at uniform density. Every never-silent flag
(``StepStats.OVERFLOW_FIELDS``: box, birth, halo, migration, ...) must stay
zero on every step — stats are per step, so each one is read — and no agent
may be lost.

The pytest process keeps its one-device view (tests/conftest.py), so all
five runs execute in one subprocess with four forced host devices.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

AGENTS_PER_SHARD = 2000
STEPS = 5
CASES = [("weak", 1), ("weak", 2), ("weak", 4), ("strong", 2), ("strong", 4)]

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import numpy as np
    from repro.core import (DistConfig, DistributedSimulation, EngineConfig,
                            ForceParams)
    from repro.core.behaviors import INFECTED, Infection, RandomWalk

    per_shard, steps, cases = json.loads(sys.argv[1])
    results = {}
    for mode, n_shards in cases:
        n = per_shard * (n_shards if mode == "weak" else 4)
        rng = np.random.default_rng(n_shards)
        side = float(np.ceil((n / 2.0) ** (1 / 3)) * 4.0)  # ~2 agents/box
        cfg = EngineConfig(capacity=n, domain_lo=(0, 0, 0),
                           domain_hi=(side,) * 3, interaction_radius=4.0,
                           dt=0.05, max_per_box=32, query_chunk=4096,
                           force=ForceParams(max_displacement=0.5))
        per = n // n_shards
        band = int(n * cfg.interaction_radius / side * 2.5) + 256
        dcfg = DistConfig(engine=cfg, n_shards=n_shards,
                          local_capacity=int(per * 1.25) + 64,
                          halo_capacity=min(band, int(per * 1.25) + 64),
                          migrate_capacity=max(256, per // 16),
                          rebalance_frequency=4)
        dsim = DistributedSimulation(dcfg, [
            RandomWalk(sigma=0.5),
            Infection(radius=4.0, beta=0.3, recovery_time=10)])
        types = np.zeros(n, np.int32)
        types[:max(n // 100, 5)] = INFECTED
        st = dsim.init_state(
            rng.uniform(1.0, side - 1.0, (n, 3)).astype(np.float32),
            diameter=np.full(n, 3.0, np.float32), agent_type=types,
            extra_init={"infect_timer": np.full(n, 10, np.int32)})
        flags, n_live = {}, []
        for _ in range(steps):
            st = dsim.step(st)
            totals = st.stats.totals(st.stats.OVERFLOW_FIELDS)
            for k, v in totals.items():
                flags[k] = flags.get(k, 0) + v
            n_live.append(int(np.asarray(st.stats["n_live"]).sum()))
        results[f"{mode}-{n_shards}"] = {"n": n, "flags": flags,
                                         "n_live": n_live}
    print("RESULT " + json.dumps(results))
""")

_CACHE = {}


def _results():
    if "res" not in _CACHE:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        arg = json.dumps([AGENTS_PER_SHARD, STEPS, CASES])
        proc = subprocess.run([sys.executable, "-c", _SCRIPT, arg], env=env,
                              capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        _CACHE["res"] = json.loads(line[len("RESULT "):])
    return _CACHE["res"]


@pytest.mark.parametrize("mode,n_shards", CASES,
                         ids=[f"{m}-{s}" for m, s in CASES])
def test_sir_raises_no_overflow_flag(mode, n_shards):
    case = _results()[f"{mode}-{n_shards}"]
    assert {"halo_overflow", "migrate_overflow", "box_overflow"} <= set(
        case["flags"]), case["flags"]
    assert not any(case["flags"].values()), case["flags"]
    assert case["n_live"] == [case["n"]] * STEPS, case["n_live"]
