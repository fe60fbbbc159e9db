"""jit'd wrappers around the Pallas kernels (sort, pack, column-map build, unsort)."""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import grid, morton
from . import collision_force as k1

BLOCK = k1.BLOCK


def colmap_span(run_capacity: int) -> int:
    """Column blocks one 3-box z-run of at most ``run_capacity`` agents can
    straddle: ⌈R/128⌉ + 1. A run longer than the span is a run longer than
    the grid's run capacity, which the engine flags as ``box_overflow``."""
    return -(-run_capacity // BLOCK) + 1


def default_colmap_width(run_capacity: int) -> int:
    """Column-map width for a grid whose 3-box z-runs hold at most
    ``run_capacity`` agents: the 9 stencil runs of a row, each straddling at
    most ``colmap_span`` column blocks, for a row block that straddles two
    (x, y) columns. A starting rung, not a bound: ``colmap_demand`` says
    what a step needed, and the capacity ladder grows the width from it."""
    return 18 * colmap_span(run_capacity)


class ColmapWork(NamedTuple):
    """What one K1 launch needed of its column map, and its work.

    overflow:  a row block needed more column blocks than the map's width
               (``demand`` above it), or a merged run more than ``span``
               blocks (a run over the grid's run capacity, which the engine
               flags as ``box_overflow`` too): possibly-missed pairs (the
               never-silent contract, DESIGN.md §4.2)
    demand:    the most column blocks any row block needed (uncapped)
    tiles:     (row block, column block) entries of the map K1 visits
    live_rows: rows whose own force K1 computes
    """
    overflow: jnp.ndarray
    demand: jnp.ndarray
    tiles: jnp.ndarray
    live_rows: jnp.ndarray


# ---------------------------------------------------------------------------
# K1: collision force
# ---------------------------------------------------------------------------

_SENTINEL = 2 ** 30


def _unique_row(ids: jnp.ndarray, maxb: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The distinct values of ascending ``ids`` below the sentinel, in order
    in the first slots of a (maxb,) row with -1 after, and their count.

    The k-th distinct value is the least id whose running count of distinct
    values passes k (the ids ascend), so the row is a comparison against
    every slot and a min, which stays inside its caller's scope on the chip
    (a scatter into the row fused into an op that carried no ``op_name``)."""
    uniq = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
    uniq &= ids < _SENTINEL
    rank = jnp.cumsum(uniq.astype(jnp.int32))
    k = jnp.arange(min(maxb, ids.shape[0]), dtype=jnp.int32)
    row = jnp.min(jnp.where(rank[None, :] > k[:, None], ids[None, :],
                            _SENTINEL), axis=1)
    row = jnp.where(row < _SENTINEL, row, -1)
    if maxb > row.shape[0]:
        row = jnp.pad(row, (0, maxb - row.shape[0]), constant_values=-1)
    return row, rank[-1]


def build_block_cols(sorted_cells: jnp.ndarray,      # (Npad, 3) int32 cells (sorted order)
                     starts: jnp.ndarray,            # (M,) per-box first sorted index
                     counts: jnp.ndarray,            # (M,)
                     row_active: jnp.ndarray,        # (Npad,) bool — needs own force
                     dims: Tuple[int, int, int],
                     maxb: int,
                     span: int = 8
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Block-sparse column map: for each 128-row block, the unique 128-wide
    column blocks covering all stencil neighbor ranges of its *active* rows.

    With the row-major linear key layout the 3×3×3 stencil is **9 merged
    ranges** (contiguous z-runs of ≤3 boxes) per row instead of 27 single-box
    ranges: 3× fewer range lookups, a 3× narrower sort when deduplicating
    block ids, and merged ranges share block boundaries — a tighter map with
    fewer ``pl.when``-skipped tiles (DESIGN.md §3.3).

    Fully-static row blocks get an empty column list — the kernel then skips
    them entirely (paper §5 static regions at block granularity).

    Returns (block_cols (n_row_blocks, maxb) int32 with -1 padding, overflow
    flag (), demand ()): the demand is the most unique column blocks any row
    block needed, uncapped. ``span`` bounds blocks per merged range (covers
    z-runs of ≤ (span − 1)·128 + 1 agents; the engine passes
    ``colmap_span`` of its run capacity); a longer range sets the overflow
    flag.
    """
    n_pad = sorted_cells.shape[0]
    n_rb = n_pad // BLOCK
    xy_off = jnp.asarray(k1_run_offsets(), jnp.int32)         # (9, 2)

    def per_row_block(blk):
        cell, act = blk                                        # (128, 3), (128,)
        nx = cell[:, None, 0] + xy_off[None, :, 0]             # (128, 9)
        ny = cell[:, None, 1] + xy_off[None, :, 1]
        inside = ((nx >= 0) & (nx < dims[0]) & (ny >= 0) & (ny < dims[1]))
        nx = jnp.clip(nx, 0, dims[0] - 1)
        ny = jnp.clip(ny, 0, dims[1] - 1)
        z_lo = jnp.maximum(cell[:, 2] - 1, 0)[:, None]
        z_hi = jnp.minimum(cell[:, 2] + 1, dims[2] - 1)[:, None]
        k_lo = morton.linear_encode3(nx, ny, jnp.broadcast_to(z_lo, nx.shape),
                                     dims)
        k_hi = morton.linear_encode3(nx, ny, jnp.broadcast_to(z_hi, nx.shape),
                                     dims)
        s = starts[k_lo]                                       # (128, 9)
        e = starts[k_hi] + counts[k_hi]
        n = jnp.where(inside & act[:, None], e - s, 0)
        b0 = s // BLOCK
        b_last = jnp.where(n > 0, (s + n - 1) // BLOCK, -1)
        ks = jnp.arange(span, dtype=jnp.int32)
        cand = b0[..., None] + ks                              # (128, 9, span)
        ok = (n[..., None] > 0) & (cand <= b_last[..., None])
        ids = jnp.sort(jnp.where(ok, cand, _SENTINEL).reshape(-1))
        out, n_uniq = _unique_row(ids, maxb)
        # span overflow: a merged range longer than span blocks would be cut
        span_ovf = jnp.any((b_last - b0 + 1) > span)
        return out, (n_uniq > maxb) | span_ovf, n_uniq

    cols, ovf, demand = jax.lax.map(
        per_row_block, (sorted_cells.reshape(n_rb, BLOCK, 3),
                        row_active.reshape(n_rb, BLOCK)),
        batch_size=min(64, max(n_rb, 1)))
    return cols, jnp.any(ovf), jnp.max(demand)


def k1_run_offsets():
    import numpy as np
    return np.array([(dx, dy) for dx in (-1, 0, 1) for dy in (-1, 0, 1)],
                    dtype=np.int32)


def build_block_cols_from_pairs(pairs: "grid.PairList",
                                row_active: jnp.ndarray,   # (Npad,) bool
                                n_pad: int,
                                maxb: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Block-sparse column map derived from a Verlet pair list (grid.PairList)
    instead of the stencil run ranges.

    For each 128-row block, the unique ascending column blocks are
    ``idx // BLOCK`` over every stored candidate of its active rows — a
    subset of what :func:`build_block_cols` would emit, since only blocks
    actually holding an in-range(+skin) candidate survive. The K1 kernel is
    unchanged: it re-tests the radius in-kernel and accumulates column blocks
    sequentially, and a dropped block's contribution is the additive identity
    (every lane masked to +0.0), so the pruned ascending map reproduces the
    streamed map's accumulation bit-exactly while skipping the ~6× of tiles
    that carry no interacting pair.

    Returns (block_cols (n_row_blocks, maxb) int32 with -1 padding, overflow
    flag (), demand ()) — same contract as build_block_cols.
    """
    c, p = pairs.idx.shape
    n_rb = n_pad // BLOCK
    lane = jnp.arange(p, dtype=jnp.int32)

    def per_row_block(i):
        rows = i * BLOCK + jnp.arange(BLOCK, dtype=jnp.int32)
        safe_rows = jnp.minimum(rows, c - 1)              # Npad ≥ c padding
        in_pool = rows < c
        act = row_active[rows] & in_pool
        idx_b = pairs.idx[safe_rows]                      # (128, P)
        stored = lane[None, :] < pairs.run_off[safe_rows, -1:]
        ok = stored & act[:, None]
        ids = jnp.sort(jnp.where(ok, idx_b // BLOCK, _SENTINEL).reshape(-1))
        out, n_uniq = _unique_row(ids, maxb)
        return out, n_uniq > maxb, n_uniq

    cols, ovf, demand = jax.lax.map(per_row_block,
                                    jnp.arange(n_rb, dtype=jnp.int32),
                                    batch_size=min(64, max(n_rb, 1)))
    return cols, jnp.any(ovf), jnp.max(demand)


@grid.sweep_scope
def collision_force_resident(position: jnp.ndarray, diameter: jnp.ndarray,
                             agent_type: jnp.ndarray, alive: jnp.ndarray,
                             active: jnp.ndarray,
                             starts: jnp.ndarray, counts: jnp.ndarray,
                             origin: jnp.ndarray, box_size: jnp.ndarray,
                             *, dims: Tuple[int, int, int], k_rep: float = 2.0,
                             adhesion: Optional[Tuple[Tuple[float, ...], ...]] = None,
                             adhesion_band: float = 0.4, maxb: int = 64,
                             span: int = 8,
                             interpret: Optional[bool] = None,
                             pairs: Optional["grid.PairList"] = None
                             ) -> tuple[jnp.ndarray, jnp.ndarray, ColmapWork]:
    """K1 over the RESIDENT grid-ordered pool: column map → kernel. No sort,
    no unsort, no candidate matrix.

    ``interpret=None`` resolves per backend: native Mosaic on TPU, interpret
    mode elsewhere (CPU CI, the shard_map host-device parity tests). Both
    engines call through here — the distributed slabs run the identical
    kernel on their local resident pool.

    Inputs must already be in grid-key order with the grid's per-box
    ``(starts, counts)`` tables (grid.build_resident) — the engine's resident
    layout means the op shares the step's one permutation instead of paying
    its own argsort and inverse scatter. The kernel traverses each row
    block's 9 merged stencil runs through the scalar-prefetched block column
    table (build_block_cols); candidates are never materialized — each grid
    step streams one 128-wide column tile through VMEM.

    active: agents whose own force is required (alive & ~static). Static
    agents still *contribute* force to active neighbors (columns, not rows);
    fully-static row blocks get an empty column list and are skipped outright
    (paper §5 at block granularity). Returns (force (C,3) f32 in resident
    order, nnz (C,) i32, ColmapWork: the column map's overflow flag and
    demand, and K1's tiles and rows). ``maxb`` is the column map's width,
    at most ``collision_force.SMEM_TABLE_ENTRIES``; ``span`` the column
    blocks one z-run may straddle (``colmap_span`` of the run capacity).

    Exactness contract (same as the engine grid, paper §3.1): ``box_size``
    must be ≥ the maximum interaction distance max(r_i + r_j) +
    adhesion_band, so every interacting pair falls inside the 3×3×3
    neighborhood.

    ``pairs`` (grid.PairList, optional): derive the column map from the
    Verlet pair list instead of the stencil ranges — only column blocks that
    hold a listed in-range(+skin) candidate are visited. Bit-exact vs the
    streamed map (build_block_cols_from_pairs); validity is the engine's
    2·pair_disp ≤ skin budget.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c = position.shape[0]
    n_pad = k1.padded_size(c, maxb)
    pad = n_pad - c

    def padded(x, fill):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill)

    sp = padded(position, 0.0)
    sact = padded(active & alive, False)

    # K1's two parts have scopes of their own (engine.SUBPHASES)
    with jax.named_scope("colmap_build"):
        if pairs is not None:
            # Verlet pair-list mode: column blocks come from the listed
            # candidates, not the full stencil ranges
            # (build_block_cols_from_pairs — bit-exact pruning, the kernel
            # itself is unchanged)
            block_cols, ovf, demand = build_block_cols_from_pairs(
                pairs, sact, n_pad, maxb)
        else:
            cells = morton.cell_of(sp, origin, box_size, dims)
            block_cols, ovf, demand = build_block_cols(
                cells, starts, counts, sact, dims, maxb, span)
        work = ColmapWork(
            overflow=ovf, demand=demand.astype(jnp.int32),
            tiles=jnp.sum((block_cols >= 0).astype(jnp.int32)),
            live_rows=jnp.sum(sact.astype(jnp.int32)))

    with jax.named_scope("k1"):
        sd = padded(diameter, 0.0)
        st = padded(agent_type, 0)
        sa = padded(alive, False)
        data_t = jnp.zeros((8, n_pad), jnp.float32)
        data_t = data_t.at[k1.ROW_X].set(sp[:, 0]).at[k1.ROW_Y].set(sp[:, 1])
        data_t = data_t.at[k1.ROW_Z].set(sp[:, 2]).at[k1.ROW_DIA].set(sd)
        data_t = data_t.at[k1.ROW_TYPE].set(st.astype(jnp.float32))
        data_t = data_t.at[k1.ROW_ALIVE].set(sa.astype(jnp.float32))

        out_t = k1.collision_force_kernel(
            data_t, block_cols, k_rep=k_rep, adhesion=adhesion,
            adhesion_band=adhesion_band, interpret=interpret)

        force = jnp.stack([out_t[k1.ROW_FX], out_t[k1.ROW_FY],
                           out_t[k1.ROW_FZ]], axis=-1)[:c]
        nnz = out_t[k1.ROW_NNZ][:c].astype(jnp.int32)
        # rows that were inactive produced zeros; also zero anything masked
        force = jnp.where(sact[:c, None], force, 0.0)
        nnz = jnp.where(sact[:c], nnz, 0)
    return force, nnz, work


def fused_resident_sweep(spec, grid_env, channels, kernels, default_mask,
                         *, origin: jnp.ndarray, box_size: jnp.ndarray,
                         k_rep: float = 2.0,
                         adhesion: Optional[Tuple[Tuple[float, ...], ...]] = None,
                         adhesion_band: float = 0.4,
                         chunk: Optional[int] = None,
                         pvary_axes: Tuple[str, ...] = (),
                         maxb: int = 64,
                         interpret: Optional[bool] = None,
                         pairs: Optional["grid.PairList"] = None):
    """Pallas-backed realization of the fused kernel-list sweep.

    Accepts the same ``grid.PairKernel`` registry as
    ``grid.resident_apply_fused``. The kernel named ``"force"`` runs in the
    K1 windowed Pallas kernel — already a single in-kernel pass over the
    resident tables with its (position, diameter, agent_type, alive)
    footprint packed into the (8, N) lane layout, so fusion for it means
    staying inside the kernel. Every other registered kernel shares ONE
    pruned XLA resident sweep over the same tables (arbitrary pair_fns don't
    lower into K1's fixed row layout). The force kernel's ``pair_fn`` is not
    invoked — K1 computes the same functional form (parity vs the XLA pair
    path is covered by tests/test_resident.py).

    Returns ``(results, work)``: results keyed like resident_apply_fused,
    work K1's ColmapWork (None when no force kernel).
    """
    results = {}
    work = None
    force_kernels = [k for k in kernels if k.name == "force"]
    rest = [k for k in kernels if k.name != "force"]
    if force_kernels:
        fk = force_kernels[0]
        active = fk.query_mask if fk.query_mask is not None else default_mask
        f, nnz, work = collision_force_resident(
            channels["position"], channels["diameter"],
            channels["agent_type"], channels["alive"], active,
            grid_env.starts, grid_env.counts, origin, box_size,
            dims=spec.dims, k_rep=k_rep, adhesion=adhesion,
            adhesion_band=adhesion_band, maxb=maxb,
            span=colmap_span(spec.run_capacity), interpret=interpret,
            pairs=pairs)
        results["force"] = {"force": f, "force_nnz": nnz}
    if rest:
        results.update(grid.resident_apply_fused(
            spec, grid_env, channels, rest, default_mask, chunk,
            pvary_axes=pvary_axes, pairs=pairs))
    return results, work


@functools.partial(jax.jit, static_argnames=(
    "dims", "k_rep", "adhesion", "adhesion_band", "maxb", "interpret"))
def collision_force(position: jnp.ndarray, diameter: jnp.ndarray,
                    agent_type: jnp.ndarray, alive: jnp.ndarray,
                    active: jnp.ndarray,
                    origin: jnp.ndarray, box_size: jnp.ndarray,
                    *, dims: Tuple[int, int, int], k_rep: float = 2.0,
                    adhesion: Optional[Tuple[Tuple[float, ...], ...]] = None,
                    adhesion_band: float = 0.4, maxb: int = 64,
                    interpret: Optional[bool] = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Slot-order compat wrapper: linear-key sort → resident core → unsort.

    For callers whose arrays are NOT already grid-ordered. The engine never
    uses this — its pool is resident (grid.build_resident) and it calls
    :func:`collision_force_resident` with the step's existing grid tables.
    Same contract and returns, in the caller's slot order.
    """
    c = position.shape[0]
    keys = morton.grid_sort_keys(position, alive, origin, box_size, dims)
    order = grid.counting_sort_order(keys, morton.linear_size(dims))
    sorted_keys = keys[order]

    starts, counts = grid.box_tables(sorted_keys, morton.linear_size(dims))

    f_sorted, nnz_sorted, work = collision_force_resident(
        position[order], diameter[order], agent_type[order], alive[order],
        (active & alive)[order], starts, counts, origin, box_size,
        dims=dims, k_rep=k_rep, adhesion=adhesion,
        adhesion_band=adhesion_band, maxb=maxb, interpret=interpret)

    force = jnp.zeros((c, 3), jnp.float32).at[order].set(f_sorted)
    nnz = jnp.zeros((c,), jnp.int32).at[order].set(nnz_sorted)
    return force, nnz, work.overflow
