"""Static-region detection — paper §5 (omit collision force calculation).

An agent is *static* next iteration iff, in the last iteration (paper
conditions i–iv):
  (i)   the agent and none of its neighbors moved,
  (ii)  neither the agent's nor any neighbor's force-relevant attributes grew
        (e.g. larger diameter),
  (iii) no new agent was added within the interaction radius, and
  (iv)  at most one neighbor force was non-zero (so removals cannot release a
        previously-cancelled force).

Per-agent flags (moved / grew / born_iter / force_nnz) are maintained by the
engine. The neighborhood conditions (i–iii) are evaluated at **box
granularity** (DESIGN.md §5): one scatter-add folds per-agent disturbance
into the dense box table, a 3×3×3 windowed OR spreads it to each box's
neighborhood, and one per-agent lookup reads the result — O(C + M) table
work, *no pairwise sweep*. Because the box edge is ≥ the interaction radius,
every agent within the radius lies inside the 3×3×3 box neighborhood, so the
box-level aggregate is a conservative superset of the paper's radius test:
an agent flagged static is static under the exact test too (never a wrong
skip); a disturbed box merely wakes a slightly larger neighborhood.

Static agents are then excluded from the force computation at *block*
granularity — on TPU per-lane predication saves nothing, so the resident
layout's query loop drops whole fully-static blocks via a dynamic trip count
(grid.resident_apply / compaction.active_block_list), and the Pallas kernel
gives fully-static row blocks an empty column list (kernels/ops).

Under the distributed engine (DESIGN.md §7) the same functions run per slab:
ghost rows ship their owner's moved/grew/born_iter/force_nnz bookkeeping, so
boundary disturbance wakes agents across shard lines. Because a disturbance
up to *two* box widths away can flip an agent's flag (the disturbed box plus
one windowed-OR spread), the distributed wrapper widens its ghost band to
2·r when ``detect_static`` is on — keeping the never-wrong-skip guarantee.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .agents import AgentPool
from .grid import GridSpec, GridState


def _window_or(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """OR of each cell with its two neighbors along ``axis`` (edge-clipped)."""
    pad = [(1, 1) if ax == axis else (0, 0) for ax in range(a.ndim)]
    p = jnp.pad(a, pad)
    n = a.shape[axis]
    lo = jax.lax.slice_in_dim(p, 0, n, axis=axis)
    mid = jax.lax.slice_in_dim(p, 1, n + 1, axis=axis)
    hi = jax.lax.slice_in_dim(p, 2, n + 2, axis=axis)
    return lo | mid | hi


def neighborhood_disturbed(spec: GridSpec, grid: GridState, pool: AgentPool,
                           iteration: jnp.ndarray) -> jnp.ndarray:
    """(M,) bool per box: any agent in its 3×3×3 neighborhood was disturbed.

    'Disturbed' = moved or grew last iteration, or was born this iteration
    (newborns also carry moved=True from the birth commit, which covers the
    cross-iteration case). Works for both resident and non-resident grids:
    ``grid.keys`` is per-slot either way. Dead slots carry DEAD_KEY, which as
    int32 is -1 and would *wrap* to the last box, not drop — clamp to the
    out-of-range sentinel ``m`` first so mode="drop" really discards them
    (belt to the ``pool.alive`` mask's suspenders).
    """
    disturbed = pool.alive & (pool.moved | pool.grew
                              | (pool.born_iter == iteration))
    m = spec.table_size
    box = jnp.minimum(grid.keys, jnp.uint32(m)).astype(jnp.int32)
    per_box = jnp.zeros((m,), jnp.int32).at[box].add(
        disturbed.astype(jnp.int32), mode="drop")
    d3 = (per_box > 0).reshape(spec.dims)
    d3 = _window_or(_window_or(_window_or(d3, 0), 1), 2)
    return d3.reshape(-1)


def update_static_flags(pool: AgentPool, spec: GridSpec, grid: GridState,
                        iteration: jnp.ndarray) -> jnp.ndarray:
    """Recompute ``static`` for every live agent (paper §5 conditions i–iv).

    Conditions i–iii via the box-granular neighborhood aggregate (conservative
    superset of the radius test, see module docstring); condition iv from the
    per-agent ``force_nnz`` bookkeeping. Cost is one scatter-add over the box
    table plus three windowed ORs — static detection no longer costs a second
    neighbor sweep, which is what makes ``detect_static=True`` a win instead
    of pure overhead on a quiescent population (a one-core CPU measurement;
    no cell runs static detection on the chip yet).
    """
    nbh = neighborhood_disturbed(spec, grid, pool, iteration)
    box = jnp.minimum(grid.keys, jnp.uint32(spec.table_size - 1)).astype(jnp.int32)
    neigh_disturbed = nbh[box]
    self_ok = ~pool.moved & ~pool.grew & (pool.born_iter != iteration)
    cond_iv = pool.force_nnz <= 1
    return pool.alive & self_ok & ~neigh_disturbed & cond_iv
