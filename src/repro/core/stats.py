"""Per-iteration statistics — one dataclass shared by both engines.

The single-device engine used a plain dict and the distributed engine grew its
own ad-hoc per-shard dict; overflow observability (DESIGN.md §4.2 — the engine
never silently drops interactions) now flows through this one structure for
both. Fields the single-device engine cannot produce (halo/migration traffic)
are simply zero there, so monitoring code is engine-agnostic.

Shapes: scalars () in the single-device engine; (n_shards,) per-shard vectors
in the distributed engine (one entry per slab). Dict-style access
(``stats["n_live"]``) is kept so existing callers and tests read either engine
the same way.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepStats:
    """Counters of one iteration (paper 'statistics' standalone operation).

    n_live:           live agents at iteration end
    n_active:         force-computed agents still alive at iteration end
                      (§5 static skipping makes this < n_live)
    births / deaths:  agents added / removed this iteration (§3.2)
    box_overflow:     grid run / hash bucket capacity exceeded —
                      possibly-missed neighbor pairs (§4.2)
    birth_overflow:   staged newborns that did not fit in capacity
    halo_overflow:    ghost-band agents that did not fit the halo buffer
                      (distributed only; §7)
    migrate_overflow: migrating agents dropped for buffer/capacity reasons
                      (distributed only; §7)
    in_flight:        owned agents still outside their slab after this step's
                      ring hop (displaced ≥2 slabs by a rebalance). Nothing
                      was dropped — they converge one hop per step — but
                      their next iteration runs with an incomplete
                      neighborhood, so the flag shares the never-silent
                      contract (distributed only; §7)
    thin_slab:        an interior slab is thinner than the ghost band, so the
                      one-hop ring cannot ship every cross-shard pair
                      (distributed only; §7). NOT fixable by growing a
                      buffer — kept separate from halo_overflow so the
                      capacity ladder knows the difference (§4.3)
    box_demand:       which-capacity provenance for box_overflow: the largest
                      observed 3-box z-run (uniform grid) or hash bucket
                      occupancy this step. The capacity ladder sizes the next
                      ``max_per_run`` / ``max_per_box`` rung directly from it
    capacity_demand:  slots the pool would have needed this step to commit
                      every staged agent (live + dropped); the ladder's
                      ``capacity`` / ``local_capacity`` rung target
    pair_overflow:    a Verlet pair-list row demanded more than
                      ``pairlist.max_pairs`` entries this build — truncated
                      candidates mean possibly-missed pairs (§4.2). The
                      ladder grows the ``max_pairs`` rung from pair_demand
    pair_demand:      which-capacity provenance for pair_overflow: the
                      largest observed per-agent in-range(+skin) candidate
                      count of the current pair list (0 when disabled)
    rebuilds:         1 if this step rebuilt its environment (grid build
                      ran), 0 if it reused a cached build (RebuildPolicy
                      mode='every_k'; grid.py): steps minus the running sum
                      counts the skips
    health:           numerical-health bitmask (health.py: NONFINITE |
                      ESCAPE | DISPLACEMENT), evaluated in-graph by the
                      iteration core. Observability only — supervisors
                      (simcheck.SupervisedRunner) act on it; run() ignores it
    sweep_slots:      lanes the fused XLA neighbor sweep evaluated its
                      pair functions on: per row of its evaluated tiles,
                      9·W on the window path and 9·R on the gather path
                      (grid.window_shape), P from the Verlet pair list.
                      float32: at 9·1,024 lanes a row, int32's 2^31
                      passes at about 233,000 rows; exact below 2^24,
                      within 6e-8 of the count above
    sweep_candidates: lanes that held a live candidate, self excluded;
                      over sweep_slots it is the share of the evaluated
                      lanes that did work. int32: 2^31 candidates is
                      about 20 M agents at 110 each
    sweep_rows:       query rows the fused XLA sweep evaluated: T per
                      tile of its visited blocks (block rows from a pair
                      list)
    sweep_window_rows: those of them on the window path (grid.py,
                      resident_apply_fused); over sweep_rows it is the
                      share the window path served. The four sweep
                      counters come from the grid tables and the pair
                      list, outside the sweep's loop
                      (grid.fused_sweep_work), and are 0 on paths that
                      run no fused XLA sweep (non-fused environments, K1
                      alone)
    colmap_overflow:  a K1 row block needed more column blocks than the
                      column map's width (EngineConfig.colmap_width), or a
                      merged stencil run more than the map's span: missed
                      column blocks mean possibly-missed pairs (§4.2). The
                      ladder grows the width from colmap_demand
    colmap_demand:    which-capacity provenance for colmap_overflow: the
                      most column blocks any K1 row block needed this step
                      (0 off the K1 path)
    k1_tiles:         (row block, column block) entries K1 visited: each
                      is one 128×128 tile of pairs
    k1_live_rows:     rows whose own force K1 computed
    """

    n_live: jnp.ndarray
    n_active: jnp.ndarray
    births: jnp.ndarray
    deaths: jnp.ndarray
    box_overflow: jnp.ndarray
    birth_overflow: jnp.ndarray
    halo_overflow: jnp.ndarray
    migrate_overflow: jnp.ndarray
    in_flight: jnp.ndarray
    thin_slab: jnp.ndarray
    box_demand: jnp.ndarray
    capacity_demand: jnp.ndarray
    pair_overflow: jnp.ndarray
    pair_demand: jnp.ndarray
    rebuilds: jnp.ndarray
    health: jnp.ndarray
    sweep_slots: jnp.ndarray
    sweep_candidates: jnp.ndarray
    sweep_rows: jnp.ndarray
    sweep_window_rows: jnp.ndarray
    colmap_overflow: jnp.ndarray
    colmap_demand: jnp.ndarray
    k1_tiles: jnp.ndarray
    k1_live_rows: jnp.ndarray

    FIELDS = ("n_live", "n_active", "births", "deaths", "box_overflow",
              "birth_overflow", "halo_overflow", "migrate_overflow",
              "in_flight", "thin_slab", "box_demand", "capacity_demand",
              "pair_overflow", "pair_demand", "rebuilds", "health",
              "sweep_slots", "sweep_candidates", "sweep_rows",
              "sweep_window_rows", "colmap_overflow", "colmap_demand",
              "k1_tiles", "k1_live_rows")

    # the §4.2 never-silent-loss flags (demands and health are not overflow)
    OVERFLOW_FIELDS = ("box_overflow", "birth_overflow", "halo_overflow",
                       "migrate_overflow", "in_flight", "thin_slab",
                       "pair_overflow", "colmap_overflow")
    # the work of the fused XLA sweep and of K1: counts, so sums over shards
    # or lanes stay counts (colmap_demand, a largest count, sums to a bound)
    SWEEP_FIELDS = ("sweep_slots", "sweep_candidates", "sweep_rows",
                    "sweep_window_rows")
    K1_FIELDS = ("colmap_demand", "k1_tiles", "k1_live_rows")
    WORK_FIELDS = SWEEP_FIELDS + K1_FIELDS

    @classmethod
    def zeros(cls, shape: tuple = ()) -> "StepStats":
        return cls(**{f: jnp.zeros(shape, jnp.float32 if f == "sweep_slots"
                                   else jnp.int32) for f in cls.FIELDS})

    # dict-style access so both engines' stats read identically
    def __getitem__(self, key: str) -> jnp.ndarray:
        if key not in self.FIELDS:
            raise KeyError(key)
        return getattr(self, key)

    def keys(self):
        return iter(self.FIELDS)

    def items(self):
        return ((f, getattr(self, f)) for f in self.FIELDS)

    def overflowed(self) -> jnp.ndarray:
        """Any never-silent-loss flag set (§4.2 contract, either engine).

        Demands (box_demand / capacity_demand) are provenance, not flags —
        they are excluded; thin_slab and in_flight are exactness flags and
        count. Traced form (usable in-graph); host code wanting a plain bool
        uses :meth:`any_overflow`."""
        total = sum((jnp.sum(getattr(self, f)) for f in self.OVERFLOW_FIELDS),
                    jnp.zeros((), jnp.int32))
        return total > 0

    def flags(self) -> Dict[str, int]:
        """Host-side: the nonzero never-silent flags, ``{field: total}``.

        Sums over shards (per-shard vectors in the distributed engine), so
        monitoring code never hand-enumerates the overflow fields again:
        ``if stats.flags(): ...`` / ``sum(stats.flags().values())``.
        """
        totals = self.totals(self.OVERFLOW_FIELDS)
        return {f: v for f, v in totals.items() if v}

    def totals(self, fields: Sequence[str]) -> Dict[str, int]:
        """Host-side: ``fields`` summed over shards or lanes, in one
        transfer."""
        values = jax.device_get([getattr(self, f) for f in fields])
        return {f: int(np.sum(v)) for f, v in zip(fields, values)}

    def any_overflow(self) -> bool:
        """Host-side bool form of :meth:`overflowed`."""
        return bool(np.asarray(self.overflowed()))

    def health_bits(self) -> int:
        """Host-side OR of the health bitmask across shards (health.py)."""
        return int(np.bitwise_or.reduce(
            np.asarray(self.health, np.int32).ravel(), initial=0))
