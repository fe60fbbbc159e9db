"""Per-phase step breakdown: fused vs unfused neighbor sweep (+ paper Fig 5).

After PR 6 amortized the grid build, steady-state step cost at the top rungs
is the neighbor sweeps: forces, each neighbor-using behavior, and statics
each streamed the pool once per phase. The fused sweep
(grid.resident_apply_fused, DESIGN.md §3.2) gathers each block's 9-run
candidate set once — pruned to the union of the registered kernels' declared
channel footprints — and evaluates every kernel against that single stream.

This benchmark times each phase standalone (jitted, compile excluded,
median µs) on a forces + SIR-infection workload (two registered kernels):

  build_us               resident grid build (permutation + tables)
  gather_us              candidate streaming alone: a reduce-only kernel
                         with the union footprint (the memory floor any
                         sweep pays at least once)
  force_us               sequential single-kernel force sweep
  behavior_us            sequential single-kernel infection sweep
  statics_us             box-granular static-flag update (no sweep — the
                         PR 3 design; kept pre-sweep because the flags gate
                         the force query mask, see DESIGN.md §3.2)
  integrate_us           displacement + clamp + write-back
  commit_us              death-compaction permutation
  fused_neighbor_us      ONE resident_apply_fused over both kernels
  unfused_neighbor_us    force_us + behavior_us (the sequential schedule)
  pairlist_build_us      grid.build_pairlist: distance-filter the fused
                         candidate stream into the packed Verlet table
                         (paid once per rebuild, amortized under skin reuse)
  pairlist_neighbor_us   the same fused sweep fed from the pair list
                         (from_pairlist mode: one width-P gather + 9 masked
                         segment rounds instead of 9 width-R streamed runs)

derived.fusion_speedup = unfused_neighbor_us / fused_neighbor_us — the
acceptance bar is >= 1.5x at >= 1M agents on the dev container.
derived.pairlist_speedup = fused_neighbor_us / pairlist_neighbor_us — the
PR 9 bar, >= 1.5x at >= 1M agents, with ``pairs_per_agent`` (mean listed
in-range candidates) and ``pruning_ratio`` (listed / streamed candidates)
recording how much of the stream the filter removes. Records
``BENCH_breakdown.json``; benchmarks/trend.py gates every per-size phase key
(they are fixed-shape standalone timings — schedule-independent, unlike the
capacity ladder's whole-step times).

Env: ``BREAKDOWN_SIZES`` (comma list, default "65536,262144,1048576" — the
small size exists so CI's reduced run compares identity-keyed against the
same committed record).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, ForceParams, Simulation
from repro.core import compaction, engine as engine_mod, forces as force_mod
from repro.core import grid as G, statics as statics_mod
from repro.core.behaviors import Infection, INFECTED

from .common import emit, random_positions, time_fn, write_bench_json


def _gather_pair_fn(reads):
    """Reduce-only kernel: touches every byte of the pruned stream, computes
    nothing else (sums survive DCE; a no-op output would let XLA drop the
    gathers entirely)."""

    def pair_fn(q, nbr, valid, q_slot):
        acc = jnp.zeros(valid.shape[0], jnp.float32)
        for ch in reads:
            x = nbr[ch].astype(jnp.float32)
            m = valid if x.ndim == 2 else valid[..., None]
            acc += jnp.sum(jnp.where(m, x, 0.0),
                           axis=tuple(range(1, x.ndim)))
        return {"g": acc}

    return pair_fn


def _one_size(n: int) -> dict:
    rng = np.random.default_rng(4)
    # ~4 live agents per box at every size (domain scales with n)
    side = float(np.ceil(4.0 * (n / 4.0) ** (1.0 / 3.0)))
    cfg = EngineConfig(capacity=n, domain_lo=(0, 0, 0), domain_hi=(side,) * 3,
                       interaction_radius=4.0, dt=0.05, max_per_box=32,
                       query_chunk=4096,
                       force=ForceParams(max_displacement=0.5))
    infection = Infection(radius=4.0, beta=0.3, recovery_time=40)
    sim = Simulation(cfg, [infection])
    pos = random_positions(rng, n, 2.0, side - 2.0)
    types = np.zeros(n, np.int32)
    types[: max(n // 100, 1)] = INFECTED
    st = sim.init_state(pos, diameter=np.full(n, 3.0, np.float32),
                        agent_type=types,
                        extra_init={"infect_timer": np.full(n, 40, np.int32)})
    spec = sim.spec
    origin = jnp.zeros(3)
    box = jnp.asarray(cfg.cell_size)

    # --- build (the resident permutation subsumes the paper's sorting) ---
    build_fn = G.make_builder(spec, method="resident")
    build = jax.jit(lambda p: build_fn(p, origin, box))
    us_build = time_fn(build, st.pool)
    bres = build(st.pool)
    rpool, gs = bres.pool, bres.grid
    channels = rpool.channels()
    alive = rpool.alive

    # --- the two registered kernels (what make_iteration_core registers) ---
    force_k, infect_k = engine_mod.registered_kernels(cfg, [infection])
    reads = G.fused_reads([force_k, infect_k])

    # sequential per-phase sweeps (EngineConfig.fused_sweep=False schedule)
    force_seq = jax.jit(lambda g, ch, m: G.resident_apply(
        spec, g, ch, m, force_k.pair_fn, force_k.out_specs))
    behav_seq = jax.jit(lambda g, ch, m: G.resident_apply(
        spec, g, ch, m, infect_k.pair_fn, infect_k.out_specs))
    seq_channels = {k: v for k, v in channels.items()
                    if not k.startswith("extra.")}
    us_force = time_fn(force_seq, gs, seq_channels, alive)
    us_behav = time_fn(behav_seq, gs, seq_channels, alive)

    # fused: ONE candidate stream for both kernels, pruned to `reads`
    fused = jax.jit(lambda g, ch, m: G.resident_apply_fused(
        spec, g, ch, [force_k, infect_k], m, cfg.query_chunk))
    us_fused = time_fn(fused, gs, channels, alive)

    # gather floor: same stream, reduce-only kernel
    gather_k = G.PairKernel("gather", _gather_pair_fn(reads),
                            {"g": ((), jnp.float32)}, reads=reads)
    gather = jax.jit(lambda g, ch, m: G.resident_apply_fused(
        spec, g, ch, [gather_k], m, cfg.query_chunk))
    us_gather = time_fn(gather, gs, channels, alive)

    # statics flags (box-granular, pre-sweep) + integration + commit
    us_statics = time_fn(
        jax.jit(lambda p, g: statics_mod.update_static_flags(
            p, spec, g, jnp.ones((), jnp.int32))), rpool, gs)
    force_out = fused(gs, channels, alive)["force"]["force"]
    dlo = jnp.asarray(cfg.domain_lo, jnp.float32)
    dhi = jnp.asarray(cfg.domain_hi, jnp.float32)
    integrate = jax.jit(lambda p, f, m: jnp.where(
        m[:, None],
        jnp.clip(p + force_mod.displacement(f, cfg.force, cfg.dt), dlo, dhi),
        p))
    us_integrate = time_fn(integrate, rpool.position, force_out, alive)
    us_commit = time_fn(jax.jit(compaction.compact), rpool)

    # --- Verlet pair list: build + the same fused sweep fed from it ---
    # max_pairs sized to the density (~17 in-range at 4/box within r=4.0);
    # demand is asserted below so an undersized table can't record a win.
    max_pairs = 64
    pl_build = jax.jit(lambda g, p, m: G.build_pairlist(
        spec, g, p, m, radius=cfg.interaction_radius, max_pairs=max_pairs,
        chunk=cfg.query_chunk))
    us_pl_build = time_fn(pl_build, gs, rpool.position, alive)
    pairs = pl_build(gs, rpool.position, alive)
    demand = int(pairs.demand)
    assert demand <= max_pairs, (
        f"pair table overflowed at n={n}: demand {demand} > {max_pairs}")
    pl_fused = jax.jit(lambda g, ch, m, pl: G.resident_apply_fused(
        spec, g, ch, [force_k, infect_k], m, cfg.query_chunk, pairs=pl))
    us_pl = time_fn(pl_fused, gs, channels, alive, pairs)

    n_live = float(jnp.sum(alive))
    pairs_per_agent = float(jnp.sum(jnp.where(alive, pairs.count, 0))) / n_live
    _, run_n = G.run_bounds(spec, gs, rpool.position)
    run_n = jnp.minimum(run_n, spec.run_capacity)
    cand = jnp.where(alive, jnp.sum(run_n, axis=-1), 0)
    cand_per_agent = float(jnp.sum(cand)) / n_live
    pruning = pairs_per_agent / max(cand_per_agent, 1e-9)

    us_unfused = us_force + us_behav
    speedup = us_unfused / max(us_fused, 1e-9)
    pl_speedup = us_fused / max(us_pl, 1e-9)
    emit(f"breakdown_n{n}_fused_neighbor", us_fused,
         f"vs unfused {us_unfused:.0f}us -> {speedup:.2f}x "
         f"(footprint {len(reads)}/{len(seq_channels)} channels)")
    emit(f"breakdown_n{n}_pairlist_neighbor", us_pl,
         f"vs streamed {us_fused:.0f}us -> {pl_speedup:.2f}x "
         f"({pairs_per_agent:.1f} pairs/agent of {cand_per_agent:.1f} "
         f"candidates, pruning {pruning:.1%}; build {us_pl_build:.0f}us)")
    emit(f"breakdown_n{n}_build", us_build, "")

    # paper Fig 5 shares (agent ops vs build vs commit), for continuity
    total = us_build + us_fused + us_integrate + us_commit
    emit(f"breakdown_n{n}_fig5_shares", total,
         f"agent_ops={(us_fused + us_integrate) / total:.1%} "
         f"(paper 76.3%) build={us_build / total:.1%} (paper 18%) "
         f"commit={us_commit / total:.1%} (paper <=2.66%)")

    return {
        "n_agents": n,
        "build_us": us_build,
        "gather_us": us_gather,
        "force_us": us_force,
        "behavior_us": us_behav,
        "statics_us": us_statics,
        "integrate_us": us_integrate,
        "commit_us": us_commit,
        "fused_neighbor_us": us_fused,
        "unfused_neighbor_us": us_unfused,
        "pairlist_build_us": us_pl_build,
        "pairlist_neighbor_us": us_pl,
        "fusion_speedup": speedup,
        "pairlist_speedup": pl_speedup,
        "pairs_per_agent": pairs_per_agent,
        "candidates_per_agent": cand_per_agent,
        "pruning_ratio": pruning,
        "pair_demand": demand,
        "max_pairs": max_pairs,
        "channels_streamed_fused": len(reads),
        "channels_streamed_unfused": len(seq_channels),
        "footprint": list(reads),
    }


def run() -> None:
    sizes = [int(s) for s in os.environ.get(
        "BREAKDOWN_SIZES", "65536,262144,1048576").split(",") if s]
    records = [_one_size(n) for n in sizes]
    write_bench_json("BENCH_breakdown.json", {
        "records": records,
        "kernels": ["force", "infection"],
        "note": "standalone jitted phase timings (compile excluded); "
                "fusion_speedup = unfused_neighbor_us / fused_neighbor_us; "
                "pairlist_speedup = fused_neighbor_us / pairlist_neighbor_us",
    })
