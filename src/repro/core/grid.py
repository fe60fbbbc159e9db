"""Uniform-grid neighbor search — paper §3.1, adapted sort-based for TPU.

BioDynaMo's grid stores each box's agents in an array-based linked list and
indexes boxes *row-major*; pointer chasing and per-box timestamps are CPU
idioms. The TPU-native formulation (DESIGN.md §2–§3):

  build:  linear (row-major) box key per agent → parallel sort by key →
          per-box (start, count) via one vectorized ``searchsorted`` over the
          dense table of exactly ``prod(dims)`` boxes. O(#agents log #agents)
          fully parallel work and O(#boxes) *vector* memset equivalents — no
          serial O(#boxes) pass, which is what the paper's timestamp trick was
          avoiding (DESIGN.md §2).
  query:  because z is the fastest-varying key axis, the 3×3×3 stencil (paper
          §3.1) collapses into **9 contiguous runs of ≤3 boxes**: 9 range
          lookups per query instead of 27 per-box lookups, and each run is a
          contiguous streaming read of the grid-ordered pool (DESIGN.md §3).

**Resident layout (DESIGN.md §3.2):** :func:`build_resident` applies the key
sort's permutation to the pool itself, so grid-key order *is* the memory
layout: no per-step sorted copy of the channels, query chunks are contiguous
slices, the paper's periodic Morton sort (§4.2) is subsumed (agents in the
same box are adjacent in memory every step), and — because dead slots carry
the maximum key — the same permutation is the §3.2 death compaction.
:func:`resident_apply` then *streams* the 9 z-runs through the pairwise
reduction one at a time (peak candidate footprint B×R instead of B×9R) and
skips fully-inactive query blocks outright via a dynamic trip count (paper §5
static regions at block granularity).

Alternative environments (paper Fig 11 comparison, DESIGN.md §11.5):
  * BruteForceEnvironment — exact O(N²) masked sweep (small N oracle).
  * ScatterGridEnvironment — 'standard' grid materializing a dense (boxes × K)
    table by scatter; models the cost of touching O(#boxes) memory that the
    paper's timestamp trick addresses.
  * HashGridEnvironment — fixed-bucket spatial hash (collisions filtered by the
    radius mask); models a memory-capped alternative. Its 27 probes stream
    through :func:`phased_chunk_apply` — same accumulation loop as the
    resident path, width K_hash per phase instead of 27·K_hash at once.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compaction, morton
from .agents import AgentPool

# 27 neighbor offsets of the 3x3x3 cube (static python constant) — used by the
# scatter/hash environments, whose tables are not contiguous in z.
_OFFSETS = np.array([(dx, dy, dz)
                     for dx in (-1, 0, 1)
                     for dy in (-1, 0, 1)
                     for dz in (-1, 0, 1)], dtype=np.int32)   # (27, 3)

# 9 xy-offsets of the 3x3x3 cube; each pairs with a contiguous z-run of 3 boxes.
_RUN_OFFSETS = np.array([(dx, dy)
                         for dx in (-1, 0, 1)
                         for dy in (-1, 0, 1)], dtype=np.int32)   # (9, 2)


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static grid configuration (hashable; part of the jit cache key)."""
    dims: Tuple[int, int, int]          # boxes per axis
    max_per_box: int = 16               # K: bound on agents in any single box
    query_chunk: int = 2048             # agents per neighbor-apply chunk
    max_per_run: Optional[int] = None   # R: gather capacity per 3-box z-run
                                        # (None → 3·K, the loosest exact bound)

    @property
    def table_size(self) -> int:
        """Exactly prod(dims) — no power-of-two padding (DESIGN.md §3)."""
        return morton.linear_size(self.dims)

    @property
    def run_capacity(self) -> int:
        """R: agents gathered per z-run. A run pools 3 boxes, so occupancy
        concentrates around 3·mean rather than 3·max — callers with measured
        densities may set ``max_per_run`` well below 3·K; the build-time
        ``max_run_count`` check keeps it exact (DESIGN.md §4.2)."""
        return self.max_per_run if self.max_per_run is not None \
            else 3 * self.max_per_box


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GridState:
    """Per-iteration neighbor index (rebuilt every step, paper Algorithm 1 L3-5)."""
    origin: jnp.ndarray        # (3,) float — grid origin (traced: domain may move)
    box_size: jnp.ndarray      # ()   float — box edge = interaction radius
    keys: jnp.ndarray          # (C,) uint32 — linear box key per slot (dead → MAX)
    order: jnp.ndarray         # (C,) int32 — slot ids sorted by key (dead at end)
    rank: jnp.ndarray          # (C,) int32 — inverse of order
    starts: jnp.ndarray        # (M,) int32 — first sorted position of each box
    counts: jnp.ndarray        # (M,) table_count_dtype(capacity): int16 when
                               #      the pool fits int16, else int32 —
                               #      values bounded by capacity (§4.3)
    max_count: jnp.ndarray     # ()   int32 — max agents in any box
    max_run_count: jnp.ndarray # ()   int32 — max agents in any 3-box z-run
                               #      (the query-exactness bound; overflow iff
                               #       > spec.run_capacity)


_DEAD_KEY = morton.DEAD_KEY


# ---------------------------------------------------------------------------
# O(N) counting-sort permutation (DESIGN.md §2) — the grid build's key sort
# ---------------------------------------------------------------------------
#
# Box keys live in [0, table_size] (the sentinel table_size stands in for
# DEAD_KEY), so a comparison sort is overkill: a counting sort — histogram the
# keys into the exact-size table, exclusive-scan the histogram into per-key
# offsets, scatter each slot to offset[key] + rank-within-key — produces the
# same stable permutation in O(N + table_size) work. Ties break by slot id,
# which makes the result *bit-exact* with jnp.argsort (stable): a stable sort
# permutation is uniquely determined by its keys, so every downstream
# guarantee that was stated over argsort (ladder-rewind bit-exactness,
# distributed parity) carries over unchanged.
#
# Two realizations, selected by ``impl``:
#   * "xla" — an in-graph LSD radix cascade: each pass histograms one
#     _DIGIT_BITS-wide digit per 1024-slot block (rank-within-digit via the
#     block-sorted segment boundaries), exclusive-scans block histograms into
#     global offsets, and applies the pass with ONE length-N scatter. Valid
#     under jit, lax.cond, and shard_map; portable to accelerators.
#   * "host" — jax.pure_callback into numpy's stable integer argsort (an LSD
#     radix cascade on these dtypes, ~3.7× faster than jnp.argsort at 16M
#     keys). OPT-IN ONLY: on jaxlib 0.4.37's CPU runtime, converting a
#     *computed* callback operand to numpy deadlocks once the copy leaves
#     the inline path (≥ ~32k elements — np.asarray/dlpack/memoryview all
#     block the same way), so the engine must never select it implicitly.
# "auto" picks "xla" everywhere; "argsort" keeps the comparison sort (oracle
# for the parity tests — measured on-par with "xla" on a CPU host, where
# XLA's variadic sort and the radix cascade are both ~3× slower than
# numpy's radix; the per-step build win comes from RebuildPolicy skipping,
# not the sort constant).

SORT_IMPLS = ("auto", "host", "xla", "argsort")

_LANE_BITS = 10
_SORT_BLOCK = 1 << _LANE_BITS        # slots per radix block (one sort row)
_DIGIT_BITS = 11                     # digit width per counting-sort pass


def _np_stable_argsort(keys: np.ndarray) -> np.ndarray:
    # pure_callback hands us a jax.Array view, not an ndarray; materialize it
    # BEFORE sorting or np.argsort's method dispatch re-enters jnp.argsort on
    # the callback thread and deadlocks the runtime once the sort is large
    # enough to leave the inline execution path
    return np.argsort(np.asarray(keys), kind="stable").astype(np.int32)


def _counting_sort_host(keys: jnp.ndarray) -> jnp.ndarray:
    return jax.pure_callback(
        _np_stable_argsort,
        jax.ShapeDtypeStruct(keys.shape, jnp.int32), keys,
        vmap_method="sequential")


def _radix_pass(vals: jnp.ndarray, order: jnp.ndarray, shift: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One stable counting-sort pass on digit ``(vals >> shift) & (D-1)``.

    vals/order are block-padded to a multiple of _SORT_BLOCK. Packing
    (digit << _LANE_BITS) | lane and value-sorting each block row yields the
    per-block stable digit order without an argsort/take_along_axis pair;
    rank-within-digit falls out of the sorted block's segment boundaries
    (one searchsorted per block — the "segment cumsum" of the counting
    sort), and the cross-block exclusive scan of the per-block histograms
    turns local ranks into global destinations. The pass is applied with a
    single length-N scatter of the inverse permutation.
    """
    n = vals.shape[0]
    nb = n // _SORT_BLOCK
    d = 1 << _DIGIT_BITS
    lane = jnp.arange(_SORT_BLOCK, dtype=jnp.uint32)
    digits = ((vals >> shift) & jnp.uint32(d - 1)).reshape(nb, _SORT_BLOCK)
    packed = jnp.sort((digits << _LANE_BITS) | lane[None, :], axis=1)
    d_sorted = (packed >> _LANE_BITS).astype(jnp.int32)           # (nb, B)
    lane_src = (packed & jnp.uint32(_SORT_BLOCK - 1)).astype(jnp.int32)

    ids = jnp.arange(d + 1, dtype=jnp.int32)
    bounds = jax.vmap(lambda row: jnp.searchsorted(row, ids))(d_sorted)
    counts_b = bounds[:, 1:] - bounds[:, :-1]                     # (nb, D)
    off_d = jnp.concatenate([jnp.zeros((1,), counts_b.dtype),
                             jnp.cumsum(counts_b.sum(axis=0))[:-1]])
    cross = jnp.cumsum(counts_b, axis=0) - counts_b               # excl. scan

    rows = jnp.arange(nb, dtype=jnp.int32)[:, None]
    j = jnp.arange(_SORT_BLOCK, dtype=jnp.int32)[None, :]
    local = j - bounds[rows, d_sorted]                            # rank in digit
    dst = (off_d[d_sorted] + cross[rows, d_sorted] + local).reshape(-1)
    src = (rows * _SORT_BLOCK + lane_src).reshape(-1)
    inv = jnp.zeros((n,), jnp.int32).at[dst].set(
        src, unique_indices=True, mode="promise_in_bounds")
    return vals[inv], order[inv]


def _counting_sort_xla(keys: jnp.ndarray, table_size: int) -> jnp.ndarray:
    c = keys.shape[0]
    nb = -(-c // _SORT_BLOCK)
    kp = jnp.pad(keys, (0, nb * _SORT_BLOCK - c), constant_values=_DEAD_KEY)
    # dead (and pad) keys → the sentinel table_size: the key domain becomes
    # [0, table_size], so bit_length(table_size) digits cover every pass. The
    # remap is monotone, and pad slots tie-break after every real slot, so
    # order[:c] is exactly the stable permutation of the original keys.
    ki = jnp.where(kp == _DEAD_KEY, jnp.uint32(table_size), kp)
    order = jnp.arange(nb * _SORT_BLOCK, dtype=jnp.int32)
    for shift in range(0, max(1, int(table_size).bit_length()), _DIGIT_BITS):
        ki, order = _radix_pass(ki, order, shift)
    return order[:c]


def counting_sort_order(keys: jnp.ndarray, table_size: int, *,
                        impl: str = "auto") -> jnp.ndarray:
    """Stable sort permutation of box keys — bit-exact with ``jnp.argsort``.

    keys: (C,) uint32 in [0, table_size] ∪ {morton.DEAD_KEY}. Returns (C,)
    int32 slot ids in ascending (key, slot) order — the unique stable
    permutation, whichever ``impl`` computes it (see SORT_IMPLS above).
    """
    if impl == "auto":
        impl = "xla"          # "host" is opt-in only (deadlock note above)
    if impl == "argsort":
        return jnp.argsort(keys).astype(jnp.int32)
    if impl == "host":
        return _counting_sort_host(keys)
    if impl == "xla":
        return _counting_sort_xla(keys, table_size)
    raise ValueError(f"sort_impl must be one of {SORT_IMPLS}, got {impl!r}")


# ---------------------------------------------------------------------------
# Rebuild policy (DESIGN.md §4) — when the per-step build may be skipped
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RebuildPolicy:
    """When the environment build runs (static; part of the jit closure).

    mode="every_step" (default): rebuild every iteration — the exact paper
    Algorithm-1 schedule, byte-identical to the engine before this knob
    existed.

    mode="every_k": reuse the previous build for up to ``k - 1`` further
    steps, as long as the accumulated per-agent displacement stays within
    ``displacement_bound``. Correctness (DESIGN.md §4.4): grid cells widen to
    ``interaction_radius + displacement_bound``, so for any current-position
    pair within the interaction radius r, the neighbor's *stale* cell (its
    cell at build time) is within one cell of the query's current cell —
    per axis |x_now(q) − x_build(n)| ≤ |x_now(q) − x_now(n)| +
    |x_now(n) − x_build(n)| ≤ r + bound = cell — hence inside the 3×3×3
    stencil. Stale candidates are a superset; pair forces read *current*
    channel values, so extra candidates beyond r contribute exactly zero.
    Any structural change (death compaction, birth commit, migration,
    arriving ghosts) marks the cached build dirty and forces a rebuild on
    the next step, so stale tables never index a reordered pool.
    """
    mode: str = "every_step"          # "every_step" | "every_k"
    k: int = 1                        # max steps served by one build
    displacement_bound: float = 0.0   # accumulated-displacement budget

    def __post_init__(self):
        if self.mode not in ("every_step", "every_k"):
            raise ValueError(
                f"rebuild.mode must be 'every_step' or 'every_k', "
                f"got {self.mode!r}")
        if not isinstance(self.k, int) or self.k < 1:
            raise ValueError(f"rebuild.k must be an int ≥ 1, got {self.k!r}")
        if self.displacement_bound < 0:
            raise ValueError(f"rebuild.displacement_bound must be ≥ 0, "
                             f"got {self.displacement_bound!r}")
        if self.mode == "every_step" and (self.k != 1
                                          or self.displacement_bound != 0.0):
            raise ValueError(
                "rebuild.k and rebuild.displacement_bound only apply under "
                "rebuild.mode='every_k' (every_step rebuilds unconditionally)")

    @property
    def cell_slack(self) -> float:
        """Extra grid-cell width the stale-build coverage argument needs."""
        return float(self.displacement_bound) if self.mode == "every_k" else 0.0


@dataclasses.dataclass(frozen=True)
class PairListConfig:
    """Static Verlet pair-list configuration (hashable; part of the jit key).

    skin:      extra filter radius beyond the interaction radius. The list is
               built at ``r + skin`` and stays a superset of every in-range
               pair while each agent's accumulated euclidean displacement
               since the build is ≤ ``skin/2`` (triangle inequality: two
               agents approaching head-on close the gap by at most
               2·(skin/2) = skin). skin=0 ⇒ the list is exact only for the
               build step, so it pairs with every-step rebuilds.
    max_pairs: P — fixed per-agent width of the index table. Demand above P
               flags ``pair_overflow`` in StepStats (never silent; the
               capacity ladder grows this rung with bit-identical rewind).
    """
    skin: float = 0.0
    max_pairs: int = 32

    def __post_init__(self):
        if self.skin < 0:
            raise ValueError(f"pairlist.skin must be ≥ 0, got {self.skin!r}")
        if not isinstance(self.max_pairs, int) or self.max_pairs < 1:
            raise ValueError(f"pairlist.max_pairs must be an int ≥ 1, "
                             f"got {self.max_pairs!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PairList:
    """Compacted per-agent candidate table (Verlet list, DESIGN.md §3.4).

    Built once per grid rebuild by :func:`build_pairlist` from the same
    streamed 3×3×3 candidate runs the fused sweep consumes, keeping only
    candidates within ``radius`` (= r + skin). Row order inside the table is
    run-major, lane-minor — exactly the order the streamed sweep accumulates
    — and ``run_off`` keeps the 9 per-run segment boundaries, so
    :func:`resident_apply_fused` can replay the identical two-level
    (per-run, then across-run) accumulation over the pruned set (dropped
    candidates contribute exact zeros; see the parity caveat there — float
    sums can still wiggle by ~1 ulp because XLA's lane reduction is
    lane-position sensitive).

    idx:     (C, P) int32 — sorted-pool candidate positions, row-packed
    run_off: (C, 10) int32 — cumulative per-run segment offsets into idx
             (off[:, 0] = 0, off[:, 9] = per-row stored count), capped at P
    count:   (C,) int32 — UNCAPPED per-row demand (provenance for the ladder)
    demand:  () int32 — max over rows of ``count``; overflow ⇔ demand > P
    """
    idx: jnp.ndarray
    run_off: jnp.ndarray
    count: jnp.ndarray
    demand: jnp.ndarray


def initial_pairlist(capacity: int, max_pairs: int) -> PairList:
    """Zero tables — what a fresh build writes for rows it never visits."""
    return PairList(idx=jnp.zeros((capacity, max_pairs), jnp.int32),
                    run_off=jnp.zeros((capacity, 10), jnp.int32),
                    count=jnp.zeros((capacity,), jnp.int32),
                    demand=jnp.zeros((), jnp.int32))


def grow_pairlist(pairs: PairList, new_capacity: int, new_max_pairs: int
                  ) -> PairList:
    """Grow a cached PairList to a larger pool capacity and/or table width.

    Ladder-rewind counterpart of :func:`grow_grid_state`: zero row/column
    padding is exactly what a pre-sized build would have written (new rows
    were never visited; columns past a row's count are never written — a
    cached list that *overflowed* is never carried, because the ladder
    rewinds the overflowing step before its post-state is kept, so the
    capped ``run_off`` never actually engaged). Supports a leading shard
    axis (distributed ladder: arrays (S, C, ...)).
    """
    old_c = pairs.count.shape[-1]
    old_p = pairs.idx.shape[-1]
    if new_capacity < old_c or new_max_pairs < old_p:
        raise ValueError(f"grow_pairlist: ({new_capacity}, {new_max_pairs}) "
                         f"< ({old_c}, {old_p})")
    if new_capacity == old_c and new_max_pairs == old_p:
        return pairs
    lead = len(pairs.count.shape) - 1
    row_pad = [(0, 0)] * lead + [(0, new_capacity - old_c)]
    return PairList(
        idx=jnp.pad(pairs.idx, row_pad + [(0, new_max_pairs - old_p)]),
        run_off=jnp.pad(pairs.run_off, row_pad + [(0, 0)]),
        count=jnp.pad(pairs.count, row_pad),
        demand=pairs.demand)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RebuildState:
    """Carried environment cache for RebuildPolicy(mode='every_k').

    grid:        the last build's GridState (tables index the pool layout as
                 of that build; the skip invariants above keep it valid)
    steps_since: () int32 — steps served by ``grid`` so far
    disp_accum:  () float32 — accumulated max per-agent per-axis |Δposition|
                 since the build (the displacement-bound budget spent)
    dirty:       () bool — a structural change invalidated ``grid``
    pairs:       cached PairList built alongside ``grid`` (None when the
                 pair-list stage is disabled — no pytree leaves, so old
                 checkpoints and sharding specs are unchanged)
    pair_disp:   () float32 — accumulated max per-agent EUCLIDEAN ‖Δposition‖
                 since the build. Separate from ``disp_accum`` (a per-axis
                 max, which does NOT bound the euclidean motion the skin
                 argument needs); list reuse requires 2·pair_disp ≤ skin.
    """
    grid: GridState
    steps_since: jnp.ndarray
    disp_accum: jnp.ndarray
    dirty: jnp.ndarray
    pairs: Optional[PairList] = None
    pair_disp: Optional[jnp.ndarray] = None


def initial_rebuild_state(spec: GridSpec, capacity: int, origin, box_size,
                          pairlist: Optional[PairListConfig] = None
                          ) -> RebuildState:
    """Pre-first-step cache: empty tables, dirty so step 0 always builds."""
    ident = jnp.arange(capacity, dtype=jnp.int32)
    cdt = table_count_dtype(capacity)    # max_* follow counts' dtype (§4.3)
    grid = GridState(
        origin=jnp.asarray(origin, jnp.float32),
        box_size=jnp.asarray(box_size, jnp.float32),
        keys=jnp.full((capacity,), _DEAD_KEY, jnp.uint32),
        order=ident, rank=ident,
        starts=jnp.zeros((spec.table_size,), jnp.int32),
        counts=jnp.zeros((spec.table_size,), cdt),
        max_count=jnp.zeros((), cdt),
        max_run_count=jnp.zeros((), cdt))
    pairs = pair_disp = None
    if pairlist is not None:
        pairs = initial_pairlist(capacity, pairlist.max_pairs)
        pair_disp = jnp.zeros((), jnp.float32)
    return RebuildState(grid=grid,
                        steps_since=jnp.zeros((), jnp.int32),
                        disp_accum=jnp.zeros((), jnp.float32),
                        dirty=jnp.ones((), bool),
                        pairs=pairs, pair_disp=pair_disp)


def grow_grid_state(grid: GridState, new_capacity: int) -> GridState:
    """Grow a cached *resident* GridState to a larger pool capacity.

    Used by the capacity-ladder rewind (host side): the pre-step state being
    re-run at the bigger rung carries this cache, and a pre-sized run at the
    new capacity would have produced exactly these arrays — dead-key padding
    keeps ``keys`` sorted, the identity order/rank extend with iota, and the
    dense tables are capacity-independent (counts only re-cast when the
    capacity crosses the int16 table dtype threshold). That is what keeps
    grown trajectories bit-identical to pre-sized ones under every_k.
    Supports a leading shard axis (distributed ladder: arrays (S, C...)).
    """
    old = grid.keys.shape[-1]
    if new_capacity == old:
        return grid
    if new_capacity < old:
        raise ValueError(f"grow_grid_state: {new_capacity} < {old}")
    pad = new_capacity - old
    lead = grid.keys.shape[:-1]
    ident_pad = jnp.broadcast_to(
        jnp.arange(old, new_capacity, dtype=jnp.int32), lead + (pad,))
    pad_widths = [(0, 0)] * len(lead) + [(0, pad)]
    cdt = table_count_dtype(new_capacity)
    return dataclasses.replace(
        grid,
        keys=jnp.pad(grid.keys, pad_widths, constant_values=_DEAD_KEY),
        order=jnp.concatenate([grid.order, ident_pad], axis=-1),
        rank=jnp.concatenate([grid.rank, ident_pad], axis=-1),
        counts=grid.counts.astype(cdt),
        max_count=grid.max_count.astype(cdt),
        max_run_count=grid.max_run_count.astype(cdt))


def table_count_dtype(capacity: int) -> jnp.dtype:
    """Dtype of per-box/per-bucket occupancy tables, capacity-parameterized.

    A box can hold at most ``capacity`` agents, so counts fit int16 whenever
    the pool does — halving the (M,)-table footprint at small ladder rungs
    (DESIGN.md §4.3). Sums of ≤3 counts (z-runs) are equally bounded by
    ``capacity`` and stay in range. Starts always need int32 (values up to
    capacity *positions*, but also used as table offsets up to M)."""
    return jnp.dtype(jnp.int16 if capacity < 2 ** 15 else jnp.int32)


def box_tables(sorted_keys: jnp.ndarray, table_size: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense per-box (starts, counts) from the key-sorted keys.

    One searchsorted over M+1 ids gives starts AND counts (ends[i]=starts[i+1];
    the M'th entry lands at n_live because dead keys sort above every box id).
    Shared with the kernel compat wrapper (kernels/ops.collision_force) so the
    table derivation exists exactly once. Counts use the capacity-
    parameterized :func:`table_count_dtype`.
    """
    box_ids = jnp.arange(table_size + 1, dtype=jnp.uint32)
    bounds = jnp.searchsorted(sorted_keys, box_ids, side="left").astype(jnp.int32)
    counts = (bounds[1:] - bounds[:-1]).astype(
        table_count_dtype(sorted_keys.shape[0]))
    return bounds[:-1], counts


def _index_tables(spec: GridSpec, sorted_keys: jnp.ndarray):
    """(starts, counts, max_count, max_run_count) from the key-sorted keys."""
    starts, counts = box_tables(sorted_keys, spec.table_size)
    # per z-run occupancy: windowed sum of 3 consecutive-z boxes
    c3 = counts.reshape(spec.dims)
    cp = jnp.pad(c3, ((0, 0), (0, 0), (1, 1)))
    runs = cp[:, :, :-2] + cp[:, :, 1:-1] + cp[:, :, 2:]
    return starts, counts, jnp.max(counts), jnp.max(runs)


def _build_sorted_impl(spec: GridSpec, pool: AgentPool, origin: jnp.ndarray,
                       box_size: jnp.ndarray, sort_impl: str = "auto"
                       ) -> GridState:
    """Build the grid index over the pool *as laid out* (non-resident).

    O(#agents) counting sort + O(#boxes) vector table derivation. Queries
    against this state gather from a key-sorted channel copy
    (``sort_channels``); the engine's hot path uses the resident build
    instead, which makes that copy the pool itself. Kept for callers that
    must preserve slot order (the Fig-11 baselines).
    """
    keys = morton.grid_sort_keys(pool.position, pool.alive, origin, box_size,
                                 spec.dims)
    order = counting_sort_order(keys, spec.table_size, impl=sort_impl)
    sorted_keys = keys[order]
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    starts, counts, max_count, max_run = _index_tables(spec, sorted_keys)
    return GridState(origin=jnp.asarray(origin), box_size=jnp.asarray(box_size),
                     keys=keys, order=order, rank=rank, starts=starts,
                     counts=counts, max_count=max_count, max_run_count=max_run)


def _build_resident_impl(spec: GridSpec, pool: AgentPool, origin: jnp.ndarray,
                         box_size: jnp.ndarray, sort_impl: str = "auto"
                         ) -> Tuple[AgentPool, GridState, jnp.ndarray]:
    """Permute the pool into grid-key order and index it **in place**.

    The one permutation (DESIGN.md §3.2) composes three reorderings the
    engine used to perform separately:
      * the grid build's key sort (agents of a box are adjacent),
      * the paper's §4.2 memory-layout sort (boxes are adjacent row-major —
        the periodic Morton sort becomes a no-op special case), and
      * §3.2 death compaction (dead slots carry ``morton.DEAD_KEY`` and sink
        stably to the tail, so live agents occupy ``[0, n_live)``).

    Returns (pool, grid, order) with ``pool`` reordered, ``grid.order``/
    ``grid.rank`` the identity (sorted position == slot id), ``grid.keys``
    already sorted, and ``order`` the applied old→new gather permutation
    (callers tracking external per-slot state re-map with it).
    """
    keys = morton.grid_sort_keys(pool.position, pool.alive, origin, box_size,
                                 spec.dims)
    order = counting_sort_order(keys, spec.table_size, impl=sort_impl)
    pool = compaction.apply_permutation(pool, order)
    sorted_keys = keys[order]
    starts, counts, max_count, max_run = _index_tables(spec, sorted_keys)
    ident = jnp.arange(order.shape[0], dtype=jnp.int32)
    grid = GridState(origin=jnp.asarray(origin), box_size=jnp.asarray(box_size),
                     keys=sorted_keys, order=ident, rank=ident, starts=starts,
                     counts=counts, max_count=max_count, max_run_count=max_run)
    return pool, grid, order


def run_bounds(spec: GridSpec, grid: GridState, query_pos: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query (start, length) of the 9 contiguous stencil z-runs.

    query_pos: (Q, 3). Returns (s, n), each (Q, 9) int32: for every (dx, dy)
    stencil column, the sorted-pool range [s, s+n) covering the z-run of ≤3
    boxes — ``[starts[k_lo], starts[k_hi]+counts[k_hi])`` with clipped
    endpoints, zero-length where the column falls outside the grid.
    Candidates are *box-level*; callers apply the radius test.
    """
    dims = spec.dims
    cell = morton.cell_of(query_pos, grid.origin, grid.box_size, dims)   # (Q,3)
    off = jnp.asarray(_RUN_OFFSETS)                                      # (9,2)
    nx = cell[:, None, 0] + off[None, :, 0]                              # (Q,9)
    ny = cell[:, None, 1] + off[None, :, 1]
    inside = ((nx >= 0) & (nx < dims[0]) & (ny >= 0) & (ny < dims[1]))
    nx = jnp.clip(nx, 0, dims[0] - 1)
    ny = jnp.clip(ny, 0, dims[1] - 1)
    z_lo = jnp.maximum(cell[:, 2] - 1, 0)[:, None]                       # (Q,1)
    z_hi = jnp.minimum(cell[:, 2] + 1, dims[2] - 1)[:, None]
    k_lo = morton.linear_encode3(nx, ny, jnp.broadcast_to(z_lo, nx.shape), dims)
    k_hi = morton.linear_encode3(nx, ny, jnp.broadcast_to(z_hi, nx.shape), dims)
    s = grid.starts[k_lo]                                                # (Q,9)
    e = grid.starts[k_hi] + grid.counts[k_hi]
    n = jnp.where(inside, e - s, 0)
    return s, n


def build_pairlist(spec: GridSpec, grid: GridState, position: jnp.ndarray,
                   alive: jnp.ndarray, *, radius, max_pairs: int,
                   chunk: Optional[int] = None,
                   pvary_axes: Tuple[str, ...] = ()) -> PairList:
    """Distance-filter the streamed candidate runs into a packed PairList.

    One pass with the exact block/run decomposition of the streamed sweep
    (same ``active_block_list`` blocks over ``alive``, same clamped slices,
    same 9 z-runs truncated at ``run_capacity``), keeping only candidates
    with ‖Δpos‖² ≤ radius² (inclusive, so behaviors that interact AT their
    radius — e.g. Infection's ``dist² ≤ r²`` — are covered at skin=0).
    Each row's kept candidates are cumsum-compacted in run-major lane-minor
    order; per-row demand past ``max_pairs`` parks in a discarded column and
    is reported uncapped through ``count``/``demand`` (§4.2 never-silent).

    ``position``/``alive`` must be the RESIDENT grid-ordered channels of the
    build (sorted position == slot id), as everywhere in this module.

    Compaction is gather-based: a row-major cumsum over the (B, 9·R) valid
    mask followed by a per-row binary search (searchsorted) for each of the
    P output lanes. A scatter formulation (``.at[dst].set``) is the obvious
    alternative but serializes element-by-element on XLA:CPU — measured
    ~20× slower than the whole pruned sweep it feeds.
    """
    c = position.shape[0]
    b = min(chunk if chunk is not None else spec.query_chunk, c)
    p = max_pairs
    r_cap = spec.run_capacity
    blk_idx, n_blk = compaction.active_block_list(alive, b)
    lane = jnp.arange(r_cap, dtype=jnp.int32)
    r2 = jnp.square(jnp.asarray(radius, position.dtype))
    out_rank = jnp.arange(1, p + 1, dtype=jnp.int32)                 # (P,)

    carry0 = (jnp.zeros((c, p), jnp.int32), jnp.zeros((c, 10), jnp.int32),
              jnp.zeros((c,), jnp.int32), jnp.zeros((), jnp.int32))
    # under shard_map: mark the carry varying on those axes (no-op for ())
    carry0 = jax.lax.pcast(carry0, pvary_axes, to="varying")

    def body(i, carry):
        idx_t, off_t, cnt_t, demand = carry
        # clamp the window so a trailing partial block stays in range; overlap
        # rows recompute identical values (pure per-row function of channels)
        sl = jnp.minimum(blk_idx[i] * b, c - b)
        rows = sl + jnp.arange(b, dtype=jnp.int32)                       # (B,)
        qpos = jax.lax.dynamic_slice_in_dim(position, sl, b, axis=0)
        arow = jax.lax.dynamic_slice_in_dim(alive, sl, b, axis=0)
        s, n = run_bounds(spec, grid, qpos)                              # (B,9)
        n = jnp.minimum(n, r_cap)

        # all 9 runs at once, run-major lane-minor: (B, 9, R) → (B, 9R)
        pos = (s[:, :, None] + lane[None, None, :]).reshape(b, 9 * r_cap)
        valid = (lane[None, None, :] < n[:, :, None]).reshape(b, 9 * r_cap)
        valid &= pos != rows[:, None]              # resident: position == slot
        valid &= arow[:, None]
        safe = jnp.where(valid, pos, 0)
        d = position[safe] - qpos[:, None, :]
        valid &= jnp.sum(d * d, axis=-1) <= r2
        inc = jnp.cumsum(valid.astype(jnp.int32), axis=1)            # (B,9R)
        cnt = inc[:, -1]                                 # uncapped demand
        # inverse of the compacting scatter: output lane m holds the source
        # lane where the running kept-count first reaches m+1
        src = jax.vmap(lambda a, v: jnp.searchsorted(a, v))(inc, out_rank[None, :].repeat(b, 0))
        stored = out_rank[None, :] <= jnp.minimum(cnt, p)[:, None]
        buf = jnp.where(stored,
                        jnp.take_along_axis(safe, jnp.minimum(src, 9 * r_cap - 1), axis=1),
                        0)
        # per-run segment boundaries: kept-count at each run's last lane
        run_end = inc.reshape(b, 9, r_cap)[:, :, -1]                 # (B,9)
        off = jnp.concatenate([jnp.zeros((b, 1), jnp.int32),
                               jnp.minimum(run_end, p)], axis=1)
        idx_t = jax.lax.dynamic_update_slice(idx_t, buf, (sl, 0))
        off_t = jax.lax.dynamic_update_slice(off_t, off, (sl, 0))
        cnt_t = jax.lax.dynamic_update_slice_in_dim(cnt_t, cnt, sl, axis=0)
        return idx_t, off_t, cnt_t, jnp.maximum(demand, jnp.max(cnt))

    idx_t, off_t, cnt_t, demand = jax.lax.fori_loop(0, n_blk, body, carry0)
    return PairList(idx=idx_t, run_off=off_t, count=cnt_t, demand=demand)


def neighbor_runs(spec: GridSpec, grid: GridState, query_pos: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate neighbors as *sorted-pool positions*, all 9 runs materialized.

    query_pos: (Q, 3). Returns (pos, valid): (Q, 9·R) int32 positions into the
    key-sorted pool and bool mask. The wide form of :func:`run_bounds` — hot
    paths stream the runs one at a time instead (:func:`resident_apply`).
    """
    r_cap = spec.run_capacity
    s, n = run_bounds(spec, grid, query_pos)
    lane = jnp.arange(r_cap, dtype=jnp.int32)                            # (R,)
    pos = s[..., None] + lane                                            # (Q,9,R)
    valid = lane < jnp.minimum(n, r_cap)[..., None]
    pos = jnp.where(valid, pos, 0)
    q = query_pos.shape[0]
    return pos.reshape(q, 9 * r_cap), valid.reshape(q, 9 * r_cap)


def neighbor_candidates(spec: GridSpec, grid: GridState, query_pos: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate neighbor *slot ids* for each query position (compat wrapper).

    query_pos: (Q, 3). Returns (ids, valid): (Q, 9·R) int32 slot ids and bool
    mask. Prefer :func:`neighbor_runs` + sorted channels on hot paths — slot
    ids re-randomize the gather order this layout exists to avoid.
    """
    pos, valid = neighbor_runs(spec, grid, query_pos)
    return grid.order[pos], valid


def sort_channels(grid: GridState, channels: Dict[str, jnp.ndarray]
                  ) -> Dict[str, jnp.ndarray]:
    """Channels reordered by grid key — neighbor runs become contiguous reads.

    Non-resident compat only (distributed engine, Fig-11 baselines): under
    :func:`build_resident` the pool itself is already in this order and no
    copy exists to make.
    """
    return {k: v[grid.order] for k, v in channels.items()}


def chunk_apply(channels: Dict[str, jnp.ndarray],
                gather_channels: Dict[str, jnp.ndarray],
                query_idx: jnp.ndarray,
                n_query: jnp.ndarray,
                cand_fn: Callable[[jnp.ndarray, jnp.ndarray],
                                  Tuple[jnp.ndarray, jnp.ndarray]],
                pair_fn: Callable[[Dict[str, jnp.ndarray],
                                   Dict[str, jnp.ndarray],
                                   jnp.ndarray, jnp.ndarray], Dict[str, jnp.ndarray]],
                out_specs: Dict[str, Tuple[Tuple[int, ...], jnp.dtype]],
                chunk: int,
                pvary_axes: Tuple[str, ...] = (),
                ) -> Dict[str, jnp.ndarray]:
    """The one chunked query loop shared by every environment (DESIGN.md §3.5).

    The chunk loop has a *dynamic* trip count ⌈n_query / chunk⌉ — with
    static-region detection on, compute really does shrink with the active set
    (paper §5 / O6; DESIGN.md §2).

    channels: full per-slot SoA dict (what q entries are sliced from).
    gather_channels: dict neighbor candidates are gathered from — the
      key-sorted copy for the uniform grid (contiguous runs), the raw slot
      view for scatter/hash/brute environments.
    query_idx: (C,) int32 — compacted active slots (tail padded, see
      compaction.active_index_list); n_query: traced count.
    cand_fn(q_pos, q_slot) -> (idx, valid): candidate indices *into
      gather_channels* and validity (self-exclusion included).
    pair_fn(q, nbr, valid, q_slot) -> dict of per-query reductions; q entries
      are (B, ...) chunk slices, nbr entries are (B, W, ...) gathers, valid is
      (B, W) bool, q_slot is (B,) the query slot ids. May return a subset of
      out_specs (missing outputs keep their zeros).
    out_specs: name → (shape_suffix, dtype) of per-agent outputs; results are
      scattered back to slot positions, zeros elsewhere.

    This is the single-phase special case of :func:`phased_chunk_apply` —
    one candidate slab of full width W instead of n_phases streamed slabs.
    """
    return phased_chunk_apply(channels, gather_channels, query_idx, n_query,
                              lambda q_pos, q_slot, j: cand_fn(q_pos, q_slot),
                              1, pair_fn, out_specs, chunk, pvary_axes)


def neighbor_apply(spec: GridSpec,
                   grid: GridState,
                   channels: Dict[str, jnp.ndarray],
                   query_idx: jnp.ndarray,
                   n_query: jnp.ndarray,
                   pair_fn: Callable,
                   out_specs: Dict[str, Tuple[Tuple[int, ...], jnp.dtype]],
                   pvary_axes: Tuple[str, ...] = (),
                   ) -> Dict[str, jnp.ndarray]:
    """Apply ``pair_fn`` over each query agent's run candidates, chunked.

    Non-resident compat path: sorts a channel copy once (the runs then gather
    contiguous spans) and resolves candidates inline per chunk. The engine's
    hot path is :func:`build_resident` + :func:`resident_apply`, which needs
    neither the copy nor the slot-id indirection.
    """
    sorted_ch = sort_channels(grid, channels)

    def cand_fn(q_pos, q_slot):
        pos, valid = neighbor_runs(spec, grid, q_pos)
        valid &= pos != grid.rank[q_slot][:, None]          # exclude self
        return pos, valid

    return chunk_apply(channels, sorted_ch, query_idx, n_query, cand_fn,
                       pair_fn, out_specs, spec.query_chunk, pvary_axes)


def resident_apply(spec: GridSpec,
                   grid: GridState,
                   channels: Dict[str, jnp.ndarray],
                   query_mask: jnp.ndarray,
                   pair_fn: Callable,
                   out_specs: Dict[str, Tuple[Tuple[int, ...], jnp.dtype]],
                   chunk: Optional[int] = None,
                   pvary_axes: Tuple[str, ...] = (),
                   ) -> Dict[str, jnp.ndarray]:
    """Run-streaming neighbor apply over the RESIDENT grid-ordered pool.

    ``channels`` must be in grid-key order (from :func:`build_resident` —
    sorted position == slot id). The loop differs from :func:`chunk_apply`
    in three load-bearing ways (DESIGN.md §3.2):

      * **Contiguous queries.** A query block is a ``dynamic_slice`` of the
        pool, not a gather through an index list; outputs are written back
        with ``dynamic_update_slice``, not scatter-add.
      * **Run streaming.** The 3×3×3 stencil is consumed as 9 sequential
        z-run gathers of width R accumulated into the per-block outputs —
        peak candidate footprint B×R instead of the B×9R materialized
        matrix, and each gather reads one contiguous span.
      * **Block-granular static skipping (paper §5 / O6).** Only blocks
        containing ≥1 ``query_mask`` row are visited: the trip count is the
        *dynamic* number of active blocks (compaction.active_block_list).
        The resident order clusters spatially-quiescent agents into the same
        blocks, which is what makes the skip rate track the static fraction.

    ``pair_fn`` outputs must be additive across splits of the candidate axis
    (sums/counts — encode an OR-style reduction as a count and threshold it).
    Outputs are written for ``query_mask`` rows, zeros elsewhere.
    """
    c = channels["position"].shape[0]
    b = min(chunk if chunk is not None else spec.query_chunk, c)
    r_cap = spec.run_capacity
    blk_idx, n_blk = compaction.active_block_list(query_mask, b)
    outs = {name: jnp.zeros((c, *sfx), dt) for name, (sfx, dt) in out_specs.items()}
    # under shard_map: mark the carry varying on those axes (no-op for ())
    outs = jax.lax.pcast(outs, pvary_axes, to="varying")
    lane = jnp.arange(r_cap, dtype=jnp.int32)

    def body(i, outs):
        # clamp the window so a trailing partial block stays in range; overlap
        # rows recompute identical values (pure per-row function of channels)
        sl = jnp.minimum(blk_idx[i] * b, c - b)
        rows = sl + jnp.arange(b, dtype=jnp.int32)                       # (B,)
        q = {k: jax.lax.dynamic_slice_in_dim(v, sl, b, axis=0)
             for k, v in channels.items()}
        qmask = jax.lax.dynamic_slice_in_dim(query_mask, sl, b, axis=0)
        s, n = run_bounds(spec, grid, q["position"])                     # (B,9)
        n = jnp.minimum(n, r_cap)

        def run(j, acc):
            pos = s[:, j, None] + lane                                   # (B,R)
            valid = lane[None, :] < n[:, j, None]
            valid &= pos != rows[:, None]          # resident: position == slot
            pos = jnp.where(valid, pos, 0)
            nbr = {k: v[pos] for k, v in channels.items()}
            res = pair_fn(q, nbr, valid, rows)
            return {name: acc[name] + res[name].astype(acc[name].dtype)
                    if name in res else acc[name] for name in acc}

        acc0 = {name: jnp.zeros((b, *sfx), dt)
                for name, (sfx, dt) in out_specs.items()}
        # inner carry must match the varying results it sums
        acc0 = jax.lax.pcast(acc0, pvary_axes, to="varying")
        acc = jax.lax.fori_loop(0, 9, run, acc0)
        new_outs = {}
        for name, val in acc.items():
            val = jnp.where(qmask.reshape((b,) + (1,) * (val.ndim - 1)), val, 0)
            new_outs[name] = jax.lax.dynamic_update_slice_in_dim(
                outs[name], val, sl, axis=0)
        return new_outs

    return jax.lax.fori_loop(0, n_blk, body, outs)


@dataclasses.dataclass(frozen=True)
class PairKernel:
    """One pair kernel registered into a fused resident sweep (DESIGN.md §3.2).

    name:      unique registry key; the fused sweep returns its outputs under
               ``results[name]``.
    pair_fn:   ``(q, nbr, valid, q_slot) -> dict`` with the same contract as
               :func:`resident_apply` — outputs must be additive across
               candidate-axis splits.
    out_specs: output name → (shape_suffix, dtype), per kernel.
    reads:     the channel *footprint* — every pool channel the pair_fn reads
               on either the query or the neighbor side (``extra.*`` names
               included). The sweep gathers exactly the union of all
               registered footprints, so an undeclared read fails loudly at
               trace time (KeyError) instead of silently streaming the whole
               SoA.
    query_mask: per-kernel query rows (None → the sweep's default mask).
               Outputs are zero outside the kernel's own mask even when a
               block was visited for another kernel's sake.
    """
    name: str
    pair_fn: Callable
    out_specs: Dict[str, Tuple[Tuple[int, ...], Any]]
    reads: Tuple[str, ...]
    query_mask: Optional[jnp.ndarray] = None


def fused_reads(kernels: Sequence["PairKernel"]) -> Tuple[str, ...]:
    """Union of the kernels' channel footprints, first-appearance order."""
    seen, order = set(), []
    for k in kernels:
        for ch in k.reads:
            if ch not in seen:
                seen.add(ch)
                order.append(ch)
    return tuple(order)


def resident_apply_fused(spec: GridSpec,
                         grid: GridState,
                         channels: Dict[str, jnp.ndarray],
                         kernels: Sequence[PairKernel],
                         default_mask: jnp.ndarray,
                         chunk: Optional[int] = None,
                         pvary_axes: Tuple[str, ...] = (),
                         pairs: Optional[PairList] = None,
                         ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Multi-kernel :func:`resident_apply`: ONE candidate stream per block.

    The single-kernel loop re-gathers the 9 z-runs once per phase (forces,
    each neighbor behavior, ...). Here every registered :class:`PairKernel`
    is evaluated against the *same* per-run gather, and that gather is pruned
    to the union of the declared footprints — an SIR run never streams
    ``diameter``, a forces-only run never streams infection timers. Peak
    per-block candidate memory drops from ``phases × B×R×|channels|`` streams
    to ``1 × B×R×|union reads|``, and the pass count over the pool from one
    per phase to one total.

    Parity vs sequential single-kernel sweeps (tests/test_fused.py):

      * The block list is driven by the OR of the kernels' query masks. A
        block visited by both paths sees the identical slice offset, run
        bounds, gather and run accumulation order, so each kernel's outputs
        on its own mask rows are **bit-exact** vs its sequential sweep.
      * A block visited only for another kernel's sake writes zeros for this
        kernel (its mask slice is all-False there) — identical to the
        sequential path never visiting it.

    **from_pairlist mode** (``pairs`` given, DESIGN.md §3.4): instead of
    streaming the 9 z-runs at width R, gather the row's pruned candidates
    ONCE at width P = pairs.idx.shape[-1] and evaluate each kernel once per
    run *segment* of the packed table. Parity vs the streamed sweep:

      * ``build_pairlist`` kept candidates in run-major lane-minor order
        with per-run boundaries (``run_off``), so each run's masked segment
        presents the surviving candidates in the streamed order with the
        dropped ones replaced by exact zeros (out-of-reach candidates
        contribute +0.0 / int 0 in every kernel — the same identity the
        streamed reduction already relies on), and the across-run
        accumulation order is identical. With skin=0 and an every-step
        rebuild the listed set is built at this step's positions, so
        per-kernel INTEGER outputs are bit-exact vs the streamed sweep and
        float outputs agree to the last bit in almost every row — but not
        unconditionally: XLA:CPU lowers the lane-axis ``jnp.sum`` inside a
        pair_fn to a lane-POSITION-sensitive partial-accumulator scheme, so
        packing bit-equal addends into different lanes (or a different
        width P ≠ R) can regroup a near-cancelling row's sum by 1-2 ulp.
        Same-mode comparisons (ladder rewind vs pre-sized, shard counts,
        the Pallas block map) share one layout and stay fully bit-exact.
      * Under every_k reuse (skin>0, 2·pair_disp ≤ skin) the listed set is
        an exact superset of the in-range pairs at *current* positions; the
        residue vs a fresh streamed sweep is float-association only (the
        same nonzero contributions may group into different run segments
        once agents cross cell lines).
    """
    if not kernels:
        return {}
    names = [k.name for k in kernels]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate PairKernel names: {names} — give each "
                         f"registered kernel (behavior) a unique name")
    reads = fused_reads(kernels)
    missing = [ch for ch in reads if ch not in channels]
    if missing:
        raise KeyError(f"PairKernel footprint names channels not in the pool: "
                       f"{missing} (have {sorted(channels)})")
    c = channels["position"].shape[0]
    b = min(chunk if chunk is not None else spec.query_chunk, c)
    r_cap = spec.run_capacity
    masks = [k.query_mask if k.query_mask is not None else default_mask
             for k in kernels]
    union_mask = masks[0]
    for m in masks[1:]:
        union_mask = union_mask | m
    blk_idx, n_blk = compaction.active_block_list(union_mask, b)
    gather_ch = {ch: channels[ch] for ch in reads}      # the pruned stream
    q_src = dict(gather_ch)
    if pairs is None:
        q_src.setdefault("position", channels["position"])  # run_bounds
    lane = jnp.arange(r_cap, dtype=jnp.int32)

    outs = {k.name: {name: jnp.zeros((c, *sfx), dt)
                     for name, (sfx, dt) in k.out_specs.items()}
            for k in kernels}
    # under shard_map: mark the carry varying on those axes (no-op for ())
    outs = jax.lax.pcast(outs, pvary_axes, to="varying")

    def acc_zeros():
        acc0 = {k.name: {name: jnp.zeros((b, *sfx), dt)
                         for name, (sfx, dt) in k.out_specs.items()}
                for k in kernels}
        # inner carry must match the varying results it sums
        return jax.lax.pcast(acc0, pvary_axes, to="varying")

    def kernel_round(q, nbr, valid, rows, accs):
        new = {}
        for k in kernels:
            res = k.pair_fn(q, nbr, valid, rows)
            acc = accs[k.name]
            new[k.name] = {
                name: acc[name] + res[name].astype(acc[name].dtype)
                if name in res else acc[name] for name in acc}
        return new

    def writeback(outs, accs, kmasks, sl):
        new_outs = {}
        for k, km in zip(kernels, kmasks):
            ko = {}
            for name, val in accs[k.name].items():
                val = jnp.where(
                    km.reshape((b,) + (1,) * (val.ndim - 1)), val, 0)
                ko[name] = jax.lax.dynamic_update_slice_in_dim(
                    outs[k.name][name], val, sl, axis=0)
            new_outs[k.name] = ko
        return new_outs

    if pairs is not None:
        p = pairs.idx.shape[-1]
        lane_p = jnp.arange(p, dtype=jnp.int32)

        def body(i, outs):
            sl = jnp.minimum(blk_idx[i] * b, c - b)
            rows = sl + jnp.arange(b, dtype=jnp.int32)                   # (B,)
            q = {ch: jax.lax.dynamic_slice_in_dim(v, sl, b, axis=0)
                 for ch, v in q_src.items()}
            kmasks = [jax.lax.dynamic_slice_in_dim(m, sl, b, axis=0)
                      for m in masks]
            idx_b = jax.lax.dynamic_slice(pairs.idx, (sl, 0), (b, p))
            off_b = jax.lax.dynamic_slice(pairs.run_off, (sl, 0), (b, 10))
            stored = lane_p[None, :] < off_b[:, -1:]
            posc = jnp.where(stored, idx_b, 0)
            nbr = {ch: v[posc] for ch, v in gather_ch.items()}  # ONE gather

            def run(j, accs):
                lo = jax.lax.dynamic_slice_in_dim(off_b, j, 1, axis=1)
                hi = jax.lax.dynamic_slice_in_dim(off_b, j + 1, 1, axis=1)
                valid = (lane_p[None, :] >= lo) & (lane_p[None, :] < hi)
                return kernel_round(q, nbr, valid, rows, accs)

            accs = jax.lax.fori_loop(0, 9, run, acc_zeros())
            return writeback(outs, accs, kmasks, sl)

        return jax.lax.fori_loop(0, n_blk, body, outs)

    def body(i, outs):
        # clamp the window so a trailing partial block stays in range; overlap
        # rows recompute identical values (pure per-row function of channels)
        sl = jnp.minimum(blk_idx[i] * b, c - b)
        rows = sl + jnp.arange(b, dtype=jnp.int32)                       # (B,)
        q = {ch: jax.lax.dynamic_slice_in_dim(v, sl, b, axis=0)
             for ch, v in q_src.items()}
        kmasks = [jax.lax.dynamic_slice_in_dim(m, sl, b, axis=0)
                  for m in masks]
        s, n = run_bounds(spec, grid, q["position"])                     # (B,9)
        n = jnp.minimum(n, r_cap)

        def run(j, accs):
            pos = s[:, j, None] + lane                                   # (B,R)
            valid = lane[None, :] < n[:, j, None]
            valid &= pos != rows[:, None]          # resident: position == slot
            pos = jnp.where(valid, pos, 0)
            nbr = {ch: v[pos] for ch, v in gather_ch.items()}  # ONE gather
            return kernel_round(q, nbr, valid, rows, accs)

        accs = jax.lax.fori_loop(0, 9, run, acc_zeros())
        return writeback(outs, accs, kmasks, sl)

    return jax.lax.fori_loop(0, n_blk, body, outs)


def phased_chunk_apply(channels: Dict[str, jnp.ndarray],
                       gather_channels: Dict[str, jnp.ndarray],
                       query_idx: jnp.ndarray,
                       n_query: jnp.ndarray,
                       phase_fn: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                                          Tuple[jnp.ndarray, jnp.ndarray]],
                       n_phases: int,
                       pair_fn: Callable,
                       out_specs: Dict[str, Tuple[Tuple[int, ...], jnp.dtype]],
                       chunk: int,
                       pvary_axes: Tuple[str, ...] = (),
                       ) -> Dict[str, jnp.ndarray]:
    """:func:`chunk_apply` with the candidate axis split into streamed phases.

    ``phase_fn(q_pos, q_slot, j)`` resolves the j'th candidate slab (idx,
    valid) of fixed width W; the inner loop accumulates ``pair_fn`` results
    across the ``n_phases`` slabs, so peak candidate footprint is B×W instead
    of B×(n_phases·W). The same additive-output contract as
    :func:`resident_apply` applies (``pair_fn`` may return a subset of
    out_specs). Used by the hash-grid environment (27 single-box probes —
    the wide form was its Fig-11 pathology) and, with ``n_phases=1``, as the
    body of :func:`chunk_apply`.
    """
    c = channels["position"].shape[0]
    b = min(chunk, c)
    n_chunks_max = (c + b - 1) // b
    # pad so dynamic_slice never clamps (clamping would desync q_slot vs lane_ok)
    qi = jnp.pad(query_idx, (0, n_chunks_max * b - c))
    outs = {name: jnp.zeros((c, *sfx), dt) for name, (sfx, dt) in out_specs.items()}
    # under shard_map: mark the carry varying on those axes (no-op for ())
    outs = jax.lax.pcast(outs, pvary_axes, to="varying")

    def body(i, outs):
        sl = i * b
        q_slot = jax.lax.dynamic_slice(qi, (sl,), (b,))                  # (B,)
        lane_ok = (sl + jnp.arange(b)) < n_query                         # (B,)
        q = {k: v[q_slot] for k, v in channels.items()}

        def phase(j, acc):
            idx, valid = phase_fn(q["position"], q_slot, j)
            valid &= lane_ok[:, None]
            nbr = {k: v[idx] for k, v in gather_channels.items()}
            res = pair_fn(q, nbr, valid, q_slot)
            return {name: acc[name] + res[name].astype(acc[name].dtype)
                    if name in res else acc[name] for name in acc}

        acc0 = {name: jnp.zeros((b, *sfx), dt)
                for name, (sfx, dt) in out_specs.items()}
        # inner carry must match the varying results it sums
        acc0 = jax.lax.pcast(acc0, pvary_axes, to="varying")
        if n_phases == 1:
            acc = phase(jnp.int32(0), acc0)
        else:
            acc = jax.lax.fori_loop(0, n_phases, phase, acc0)
        new_outs = {}
        for name, val in acc.items():
            val = jnp.where(
                lane_ok.reshape((b,) + (1,) * (val.ndim - 1)), val, 0)
            new_outs[name] = outs[name].at[q_slot].add(
                val.astype(outs[name].dtype), mode="drop")
        return new_outs

    n_chunks = jnp.minimum((n_query + b - 1) // b, n_chunks_max)
    return jax.lax.fori_loop(0, n_chunks, body, outs)


# ---------------------------------------------------------------------------
# Alternative environments (Fig 11 comparison)
# ---------------------------------------------------------------------------

def brute_force_apply(channels: Dict[str, jnp.ndarray],
                      alive: jnp.ndarray,
                      pair_fn,
                      out_specs,
                      chunk: int = 512) -> Dict[str, jnp.ndarray]:
    """Exact O(N²) neighbor apply (oracle + Fig-11 baseline).

    pair_fn has the same signature as in neighbor_apply; candidates are *all*
    agents (``valid`` carries alive & not-self; the radius test is pair_fn's
    own distance mask, identical to the grid path).
    """
    c = channels["position"].shape[0]
    chunk = min(chunk, c)
    ids = jnp.arange(c, dtype=jnp.int32)

    def cand_fn(q_pos, q_slot):
        b = q_slot.shape[0]
        idx = jnp.broadcast_to(ids[None], (b, c))
        valid = alive[None, :] & (idx != q_slot[:, None])
        return idx, valid

    q_idx = jnp.arange(c, dtype=jnp.int32)
    return chunk_apply(channels, channels, q_idx, jnp.int32(c), cand_fn,
                       pair_fn, out_specs, chunk)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScatterGridState:
    """'Standard implementation' grid: dense (boxes × K) member table via scatter.

    Models BioDynaMo's *unoptimized* path: the table is re-zeroed and re-scattered
    every iteration, touching O(#boxes · K) memory — the cost the paper's
    timestamp trick (and our sort-based build) avoids.
    """
    origin: jnp.ndarray
    box_size: jnp.ndarray
    table: jnp.ndarray         # (M, K) int32 slot ids, -1 = empty
    counts: jnp.ndarray        # (M,)


def _build_scatter_impl(spec: GridSpec, pool: AgentPool, origin, box_size,
                        sort_impl: str = "auto") -> ScatterGridState:
    m, k = spec.table_size, spec.max_per_box
    keys = morton.linear_keys(pool.position, origin, box_size, spec.dims)
    keys = jnp.where(pool.alive, keys, m)  # park dead at row m (dropped)
    # slot-within-box via sort (the CPU version uses sequential insertion;
    # the data-parallel equivalent needs a sort or atomics — we sort).
    order = counting_sort_order(keys, m, impl=sort_impl)
    sorted_keys = keys[order]
    first = jnp.searchsorted(sorted_keys, sorted_keys, side="left")
    slot_in_box = jnp.arange(keys.shape[0]) - first                  # rank within box
    table = jnp.full((m + 1, k), -1, jnp.int32)
    sk = jnp.minimum(slot_in_box, k - 1)
    table = table.at[sorted_keys.astype(jnp.int32), sk].set(order.astype(jnp.int32),
                                                            mode="drop")
    counts = jnp.zeros((m + 1,), jnp.int32).at[keys.astype(jnp.int32)].add(
        pool.alive.astype(jnp.int32), mode="drop")
    return ScatterGridState(origin=jnp.asarray(origin), box_size=jnp.asarray(box_size),
                            table=table[:m], counts=counts[:m])


def scatter_grid_candidates(spec: GridSpec, g: ScatterGridState, query_pos
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = spec.max_per_box
    cell = morton.cell_of(query_pos, g.origin, g.box_size, spec.dims)
    ncell = cell[:, None, :] + jnp.asarray(_OFFSETS)[None, :, :]
    dims = jnp.asarray(spec.dims, jnp.int32)
    inside = jnp.all((ncell >= 0) & (ncell < dims), axis=-1)
    ncell_c = jnp.clip(ncell, 0, dims - 1)
    codes = morton.linear_encode3(ncell_c[..., 0], ncell_c[..., 1],
                                  ncell_c[..., 2], spec.dims).astype(jnp.int32)
    members = g.table[codes]                                      # (Q,27,K)
    valid = (members >= 0) & inside[..., None]
    q = query_pos.shape[0]
    return jnp.maximum(members, 0).reshape(q, 27 * k), valid.reshape(q, 27 * k)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HashGridState:
    """Spatial-hash grid with a fixed bucket table (memory-capped alternative).

    ``cell_keys`` holds each slot's *unhashed* linear cell id (dead slots →
    DEAD_KEY): a bucket mixes agents from every cell that hashes to it, so
    queries must re-check the candidate's true cell against the probed
    stencil cell — without it, two stencil cells colliding into one bucket
    would yield the bucket's in-radius agents twice (double-counted force
    and force_nnz).
    """
    origin: jnp.ndarray
    box_size: jnp.ndarray
    keys: jnp.ndarray
    cell_keys: jnp.ndarray
    order: jnp.ndarray
    starts: jnp.ndarray
    counts: jnp.ndarray
    max_bucket_count: jnp.ndarray


# default probe gather width multiplier: hash collisions inflate buckets, so
# queries gather HASH_K_MULT×max_per_box per bucket; a bucket fuller than that
# truncates → flagged via stats["box_overflow"] (engine, DESIGN.md §4.2)
HASH_K_MULT = 4


def _hash_cell(cell: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    # classic 3-prime spatial hash (Teschner et al.)
    p = jnp.asarray([73856093, 19349663, 83492791], jnp.uint32)
    h = (cell[..., 0].astype(jnp.uint32) * p[0]
         ^ cell[..., 1].astype(jnp.uint32) * p[1]
         ^ cell[..., 2].astype(jnp.uint32) * p[2])
    return h % jnp.uint32(n_buckets)


def _build_hash_impl(spec: GridSpec, pool: AgentPool, origin, box_size,
                     n_buckets: int = 1 << 14, sort_impl: str = "auto"
                     ) -> HashGridState:
    cell = morton.cell_of(pool.position, origin, box_size, spec.dims)
    keys = _hash_cell(cell, n_buckets)
    keys = jnp.where(pool.alive, keys, jnp.uint32(n_buckets))
    cell_keys = jnp.where(pool.alive,
                          morton.linear_encode3(cell[..., 0], cell[..., 1],
                                                cell[..., 2], spec.dims),
                          morton.DEAD_KEY)
    order = counting_sort_order(keys, n_buckets, impl=sort_impl)
    sorted_keys = keys[order]
    bucket_ids = jnp.arange(n_buckets, dtype=jnp.uint32)
    starts = jnp.searchsorted(sorted_keys, bucket_ids, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(sorted_keys, bucket_ids, side="right").astype(jnp.int32)
    counts = (ends - starts).astype(table_count_dtype(pool.capacity))
    return HashGridState(origin=jnp.asarray(origin), box_size=jnp.asarray(box_size),
                         keys=keys, cell_keys=cell_keys, order=order,
                         starts=starts, counts=counts,
                         max_bucket_count=jnp.max(counts))


def hash_grid_probe(spec: GridSpec, g: HashGridState, query_pos,
                    j: jnp.ndarray, k_mult: int = HASH_K_MULT
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Candidates of the j'th stencil box only — one streamed hash probe.

    phase_fn for :func:`phased_chunk_apply` (27 phases): capacity per probe is
    one bucket (k_mult·max_per_box), not 27 buckets at once. This is the fix
    for the Fig-11 hash-grid pathology: the wide (Q, 27·K_hash) candidate
    matrix was ~12× the uniform grid's and dominated its search time.

    Candidates are filtered to the probed cell's true members (``cell_keys``
    re-check): without it, two stencil cells hashing to one bucket would
    double-count the bucket's in-radius agents across phases.
    """
    n_buckets = g.starts.shape[0]       # from the build — no mismatch possible
    k = spec.max_per_box * k_mult
    cell = morton.cell_of(query_pos, g.origin, g.box_size, spec.dims)    # (Q,3)
    ncell = cell + jnp.asarray(_OFFSETS)[j][None, :]
    dims = jnp.asarray(spec.dims, jnp.int32)
    inside = jnp.all((ncell >= 0) & (ncell < dims), axis=-1)
    ncell_c = jnp.clip(ncell, 0, dims - 1)
    h = _hash_cell(ncell_c, n_buckets)
    k_true = morton.linear_encode3(ncell_c[..., 0], ncell_c[..., 1],
                                   ncell_c[..., 2], spec.dims)           # (Q,)
    s = g.starts[h]
    n = jnp.where(inside, g.counts[h], 0)
    lane = jnp.arange(k, dtype=jnp.int32)
    pos = s[:, None] + lane
    valid = lane < jnp.minimum(n, k)[:, None]
    pos = jnp.where(valid, pos, 0)
    ids = g.order[pos]                                                   # (Q,k)
    valid &= g.cell_keys[ids] == k_true[:, None]
    return ids, valid


def hash_grid_candidates(spec: GridSpec, g: HashGridState, query_pos,
                         k_mult: int = HASH_K_MULT
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Wide (Q, 27·k) candidate matrix: all 27 probes of
    :func:`hash_grid_probe` materialized at once. Fig-11 baseline only
    ('hash_grid_wide' — its width is the pathology the streamed probes fix);
    kept as a thin stack over the probe so the two paths cannot diverge.
    """
    probes = [hash_grid_probe(spec, g, query_pos, j, k_mult)
              for j in range(27)]
    return (jnp.concatenate([ids for ids, _ in probes], axis=1),
            jnp.concatenate([valid for _, valid in probes], axis=1))


# ---------------------------------------------------------------------------
# Unified builder factory — ONE entry point over the grid-build zoo
# ---------------------------------------------------------------------------

BUILD_METHODS = ("resident", "sorted", "scatter", "hash")


class BuildResult(NamedTuple):
    """Uniform result of every grid build (whatever the method).

    pool:     the pool the tables index — permuted into grid-key order by
              the resident method, returned unchanged by the others
    grid:     GridState ('resident'/'sorted'), ScatterGridState, or
              HashGridState
    order:    (C,) int32 old→new gather permutation *applied to the pool*
              (identity for the non-permuting methods) — callers tracking
              external per-slot state re-map with it
    overflow: () int32 — agents beyond the method's fixed gather/table
              capacity this build: run_capacity excess for the uniform grid,
              per-box truncation for the scatter table (which the legacy
              entry point dropped silently), probe-width excess for the hash
              grid. 0 ⇔ queries against this build are exact.
    demand:   () int32 — the observed peak occupancy behind ``overflow``
              (max 3-box z-run / max box / max bucket): the which-capacity
              provenance the capacity ladder sizes the next rung from.
    """
    pool: AgentPool
    grid: Any
    order: jnp.ndarray
    overflow: jnp.ndarray
    demand: jnp.ndarray


def make_builder(spec: GridSpec, *, method: str = "resident",
                 sort_impl: str = "auto", n_buckets: int = 1 << 14
                 ) -> Callable[[AgentPool, jnp.ndarray, jnp.ndarray],
                               BuildResult]:
    """The one grid-builder entry point (replaces the build_* zoo).

    Returns ``build_fn(pool, origin, box_size) -> BuildResult`` for the
    chosen method, with a common overflow/demand surface (§4.2 never-silent
    contract) regardless of which underlying structure is built:

      * "resident" — counting-sort permutation applied to the pool itself;
        grid order IS memory order (the engine hot path).
      * "sorted"   — same tables over the pool as laid out (slot order
        preserved; queries gather through ``sort_channels``).
      * "scatter"  — dense (boxes × K) member table via scatter (the
        paper's 'standard implementation' baseline).
      * "hash"     — fixed-bucket spatial hash over ``n_buckets`` buckets.

    sort_impl selects the key-sort realization (SORT_IMPLS): the O(N)
    counting sort on its "xla" (in-graph, the "auto" default) and "host"
    (opt-in callback — see the deadlock note above) paths, "argsort" as
    the comparison-sort oracle.
    """
    if method not in BUILD_METHODS:
        raise ValueError(
            f"method must be one of {BUILD_METHODS}, got {method!r}")
    if sort_impl not in SORT_IMPLS:
        raise ValueError(
            f"sort_impl must be one of {SORT_IMPLS}, got {sort_impl!r}")

    if method in ("resident", "sorted"):
        def build_fn(pool: AgentPool, origin, box_size) -> BuildResult:
            if method == "resident":
                pool, grid, order = _build_resident_impl(
                    spec, pool, origin, box_size, sort_impl)
            else:
                grid = _build_sorted_impl(spec, pool, origin, box_size,
                                          sort_impl)
                order = jnp.arange(pool.capacity, dtype=jnp.int32)
            demand = grid.max_run_count.astype(jnp.int32)
            return BuildResult(pool, grid, order,
                               jnp.maximum(demand - spec.run_capacity, 0),
                               demand)
    elif method == "scatter":
        def build_fn(pool: AgentPool, origin, box_size) -> BuildResult:
            grid = _build_scatter_impl(spec, pool, origin, box_size,
                                       sort_impl)
            demand = jnp.max(grid.counts).astype(jnp.int32)
            return BuildResult(pool, grid,
                               jnp.arange(pool.capacity, dtype=jnp.int32),
                               jnp.maximum(demand - spec.max_per_box, 0),
                               demand)
    else:
        def build_fn(pool: AgentPool, origin, box_size) -> BuildResult:
            grid = _build_hash_impl(spec, pool, origin, box_size, n_buckets,
                                    sort_impl)
            demand = grid.max_bucket_count.astype(jnp.int32)
            return BuildResult(pool, grid,
                               jnp.arange(pool.capacity, dtype=jnp.int32),
                               jnp.maximum(
                                   demand - HASH_K_MULT * spec.max_per_box,
                                   0),
                               demand)
    return build_fn


# -- one-release deprecation shims over the legacy direct entry points -------

class GridBuilderDeprecationWarning(DeprecationWarning):
    """A legacy direct grid-build entry point was called (use make_builder).

    Its own category so CI can promote exactly these to errors
    (``-W error::repro.core.grid.GridBuilderDeprecationWarning``) without
    entangling unrelated DeprecationWarnings from dependencies.
    """


def _builder_deprecated(name: str, repl: str) -> None:
    warnings.warn(
        f"grid.{name} is deprecated and will be removed next release; use "
        f"grid.make_builder(spec, method={repl!r}) instead",
        GridBuilderDeprecationWarning, stacklevel=3)


def build(spec: GridSpec, pool: AgentPool, origin: jnp.ndarray,
          box_size: jnp.ndarray) -> GridState:
    """Deprecated: ``make_builder(spec, method='sorted')(...).grid``."""
    _builder_deprecated("build", "sorted")
    return _build_sorted_impl(spec, pool, origin, box_size)


def build_resident(spec: GridSpec, pool: AgentPool, origin: jnp.ndarray,
                   box_size: jnp.ndarray
                   ) -> Tuple[AgentPool, GridState, jnp.ndarray]:
    """Deprecated: ``make_builder(spec, method='resident')`` → BuildResult."""
    _builder_deprecated("build_resident", "resident")
    return _build_resident_impl(spec, pool, origin, box_size)


def build_scatter_grid(spec: GridSpec, pool: AgentPool, origin, box_size
                       ) -> ScatterGridState:
    """Deprecated: ``make_builder(spec, method='scatter')(...).grid``."""
    _builder_deprecated("build_scatter_grid", "scatter")
    return _build_scatter_impl(spec, pool, origin, box_size)


def build_hash_grid(spec: GridSpec, pool: AgentPool, origin, box_size,
                    n_buckets: int = 1 << 14) -> HashGridState:
    """Deprecated: ``make_builder(spec, method='hash')(...).grid``."""
    _builder_deprecated("build_hash_grid", "hash")
    return _build_hash_impl(spec, pool, origin, box_size, n_buckets)
