"""Distributed weak/strong scaling — TeraAgent-direction record (DESIGN.md §7).

Runs the shard_map engine (every slab executing the shared iteration core)
over 1..8 host-platform devices and records per-step timing to
``BENCH_distributed.json``:

  * **weak scaling**: fixed agents/shard, shards ∈ {1, 2, 4, 8} — the default
    per-shard population makes the 8-shard point a ≥1M-agent run.
  * **strong scaling**: fixed total population across shards ∈ {2, 4, 8},
    plus the fitted log-log slope of time vs shards (−1 would be ideal; on
    this container all "devices" share one physical core, so the honest
    expectation is ≈ 0 — the record tracks the *trend* across PRs and real
    multi-core/TPU runs).

Any halo/migration/box overflow flag fails the run (exit 1) — the §4.2
never-silent-loss contract extends to benchmarks.

Must run as its own process: on the CPU (``JAX_PLATFORMS=cpu``) it forces
eight host devices before importing jax, elsewhere it uses the devices JAX
finds, and a shard count above the device count fails the run:

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m benchmarks.distributed

Env overrides for CI smoke: DIST_BENCH_AGENTS_PER_SHARD, DIST_BENCH_SHARDS
(comma-separated), DIST_BENCH_STEPS.
"""

from __future__ import annotations

import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import numpy as np  # noqa: E402

AGENTS_PER_SHARD = int(os.environ.get("DIST_BENCH_AGENTS_PER_SHARD", 131_072))
SHARD_COUNTS = tuple(int(s) for s in
                     os.environ.get("DIST_BENCH_SHARDS", "1,2,4,8").split(","))
N_STEPS = int(os.environ.get("DIST_BENCH_STEPS", 3))


def _flags(state) -> int:
    """All never-silent flags of one step (stats are per-step, not
    cumulative — every step must be inspected)."""
    return sum(state.stats.flags().values())


def _step_time(dsim, state, n_steps: int) -> tuple:
    """(median wall ms/step, overflow flag count, final state), after one
    warm (compile) step."""
    import jax
    state = dsim.step(state)
    jax.block_until_ready(state.channels["position"])
    overflow = _flags(state)
    times = []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        state = dsim.step(state)
        jax.block_until_ready(state.channels["position"])
        times.append(time.perf_counter() - t0)
        overflow += _flags(state)
    return float(np.median(times) * 1e3), overflow, state


def _run_case(n_shards: int, n_total: int) -> dict:
    import jax
    from repro.core import DistConfig, DistributedSimulation, EngineConfig, ForceParams

    rng = np.random.default_rng(n_shards)
    # constant density ≈ 2 agents/box at r=4 (same regime as BENCH_scaling)
    side = float(np.ceil((n_total / 2.0) ** (1 / 3)) * 4.0)
    cfg = EngineConfig(capacity=n_total, domain_lo=(0, 0, 0),
                       domain_hi=(side,) * 3, interaction_radius=4.0,
                       dt=0.05, max_per_box=32, query_chunk=4096,
                       force=ForceParams(max_displacement=0.5))
    per = n_total // n_shards
    # ghost band ≈ (r/side)·n_total agents per face at uniform density; ×2.5
    # headroom covers quantile-slab density variation (overflow still flagged)
    band = int(n_total * cfg.interaction_radius / side * 2.5) + 256
    dcfg = DistConfig(engine=cfg, n_shards=n_shards,
                      local_capacity=int(per * 1.25) + 64,
                      halo_capacity=min(band, int(per * 1.25) + 64),
                      migrate_capacity=max(256, per // 16),
                      rebalance_frequency=4)
    dsim = DistributedSimulation(dcfg)
    pos = rng.uniform(1.0, side - 1.0, (n_total, 3)).astype(np.float32)
    state = dsim.init_state(pos, diameter=np.full(n_total, 3.0, np.float32))
    ms, overflow, state = _step_time(dsim, state, N_STEPS)
    n_live = int(np.asarray(state.stats["n_live"]).sum())
    del state, dsim
    return {"n_shards": n_shards, "n_agents": n_total, "side": side,
            "ms_per_step": ms, "agents_per_sec": n_total / (ms / 1e3),
            "n_live": n_live, "overflow": overflow}


def run() -> None:
    import jax
    devices = jax.devices()
    n_dev = len(devices)
    if max(SHARD_COUNTS) > n_dev:
        raise SystemExit(f"shard counts {SHARD_COUNTS} need "
                         f"{max(SHARD_COUNTS)} devices; found {n_dev} "
                         f"{devices[0].platform} device(s)")
    shard_counts = list(SHARD_COUNTS)
    record = {"device_count": n_dev, "backend": jax.default_backend(),
              "agents_per_shard": AGENTS_PER_SHARD,
              "weak": [], "strong": []}
    failures = 0

    for s in shard_counts:
        case = _run_case(s, AGENTS_PER_SHARD * s)
        record["weak"].append(case)
        failures += case["overflow"]
        print(f"weak  shards={s} n={case['n_agents']:>9} "
              f"{case['ms_per_step']:9.1f} ms/step "
              f"({case['agents_per_sec']:.3g} agents/s)")

    n_strong = AGENTS_PER_SHARD * max(shard_counts)
    for s in [s for s in shard_counts if s > 1]:
        case = _run_case(s, n_strong)
        record["strong"].append(case)
        failures += case["overflow"]
        print(f"strong shards={s} n={case['n_agents']:>9} "
              f"{case['ms_per_step']:9.1f} ms/step")

    if len(record["strong"]) >= 2:
        ls = np.log([c["n_shards"] for c in record["strong"]])
        lt = np.log([c["ms_per_step"] for c in record["strong"]])
        record["strong_loglog_slope"] = float(np.polyfit(ls, lt, 1)[0])
        print(f"strong scaling log-log slope: "
              f"{record['strong_loglog_slope']:.3f} (ideal -1; "
              f"~0 expected on a single shared core)")
    if len(record["weak"]) >= 2:
        t0 = record["weak"][0]["ms_per_step"]
        record["weak_efficiency"] = {
            str(c["n_shards"]): t0 / c["ms_per_step"] for c in record["weak"]}

    from benchmarks.common import write_bench_json
    write_bench_json("BENCH_distributed.json", record)
    if failures:
        raise SystemExit(f"overflow flags raised during benchmark: {failures}")


if __name__ == "__main__":
    run()
