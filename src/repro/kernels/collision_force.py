"""K1: tiled pairwise collision force — Pallas TPU kernel.

The paper identifies the pairwise mechanical force as the dominant cost (§5).
On TPU we exploit the resident grid-key layout (row-major linear keys,
DESIGN.md §3): the pool arrives already in key order (grid.build_resident),
so each grid box — and each 3-box z-run of the stencil — is contiguous, and
the candidate neighbors of a *block* of 128 consecutive agents live in a
small set of 128-wide column blocks. The engine derives a scalar-prefetched
run table — the block-sparse column map of the 9 merged stencil runs
(ops.build_block_cols) — and the kernel traverses it per row block,
(row_block × listed col_blocks), computing a 128×128 pairwise force tile in
VMEM per step: candidates are never materialized in HBM —
flash-attention-like structure with VPU math instead of MXU.

Correctness does not depend on the column map being tight: any pair within the
interaction radius is necessarily inside the 27-box neighborhood (box ≥ radius),
and the map covers those ranges, so extra candidates are masked by the radius
test. Sentinel (-1) column entries are skipped with ``pl.when`` — the same
block-skipping mechanism that realizes the paper's static-region optimization
at block granularity (DESIGN.md §2/O6).

SMEM bound: the scalar-prefetched table lives in SMEM (1 MiB on v5e), but
the pool's capacity sets its length (N_pad/128 × maxb int32 — 16 MiB at
8,388,608 slots). The table is therefore walked in slices of at most
``SMEM_TABLE_ENTRIES`` entries, one kernel launch per slice under
``lax.map``. Any change to the table's layout must keep one launch's slice
inside SMEM at every capacity the ladder can reach.

Data layout (TPU-friendly): agents are packed along *lanes*:
  data_t: (8, N_pad) f32 rows = [x, y, z, diameter, type, alive, 0, 0]
  out_t:  (8, N_pad) f32 rows = [fx, fy, fz, nnz, 0, 0, 0, 0]
so each (8, 128) tile is one native VREG tile set.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128
ROW_X, ROW_Y, ROW_Z, ROW_DIA, ROW_TYPE, ROW_ALIVE = 0, 1, 2, 3, 4, 5
ROW_FX, ROW_FY, ROW_FZ, ROW_NNZ = 0, 1, 2, 3
SMEM_TABLE_ENTRIES = 1 << 15   # column-table entries per kernel launch
                               # (128 KiB of int32 in SMEM)


def _force_tile(row: jnp.ndarray, col: jnp.ndarray,
                row_base: jnp.ndarray, col_base: jnp.ndarray,
                k_rep: float, adhesion: Optional[Tuple[Tuple[float, ...], ...]],
                adhesion_band: float) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(8,128) row tile × (8,128) col tile → per-row (fx, fy, fz, nnz)."""
    rx, ry, rz = row[ROW_X], row[ROW_Y], row[ROW_Z]        # (128,)
    cx, cy, cz = col[ROW_X], col[ROW_Y], col[ROW_Z]
    dx = cx[None, :] - rx[:, None]                          # (128,128) q->n
    dy = cy[None, :] - ry[:, None]
    dz = cz[None, :] - rz[:, None]
    dist2 = dx * dx + dy * dy + dz * dz
    dist = jnp.sqrt(jnp.maximum(dist2, 1e-18))
    r_q = row[ROW_DIA][:, None] * 0.5
    r_n = col[ROW_DIA][None, :] * 0.5
    delta = r_q + r_n - dist
    r_eff = jnp.maximum(r_q * r_n / jnp.maximum(r_q + r_n, 1e-12), 1e-12)
    f_rep = k_rep * jnp.sqrt(r_eff) * jnp.power(jnp.maximum(delta, 0.0), 1.5)
    if adhesion is not None:
        # tiny type-count: unroll the (T,T) adhesion table as select terms
        t = len(adhesion)
        ti = row[ROW_TYPE][:, None]
        tj = col[ROW_TYPE][None, :]
        mu = jnp.zeros_like(dist)
        for a in range(t):
            for b in range(t):
                coeff = adhesion[a][b]
                if coeff != 0.0:
                    mu += coeff * ((ti == a) & (tj == b)).astype(dist.dtype)
        band = jnp.maximum(delta + adhesion_band, 0.0)
        f_adh = jnp.where(delta + adhesion_band > 0.0, mu * jnp.sqrt(r_eff * band), 0.0)
    else:
        f_adh = jnp.zeros_like(dist)
    f_mag = f_rep - f_adh

    row_ids = row_base + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    col_ids = col_base + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    valid = ((row[ROW_ALIVE][:, None] > 0.5) & (col[ROW_ALIVE][None, :] > 0.5)
             & (row_ids != col_ids) & (delta + adhesion_band > 0.0))
    f = jnp.where(valid, -f_mag, 0.0)
    inv = 1.0 / dist
    fx = jnp.sum(f * dx * inv, axis=1)
    fy = jnp.sum(f * dy * inv, axis=1)
    fz = jnp.sum(f * dz * inv, axis=1)
    mag2 = f * f
    nnz = jnp.sum((mag2 > (1e-7) ** 2).astype(jnp.float32), axis=1)
    return fx, fy, fz, nnz


def _kernel(base_ref,            # scalar prefetch: (1,) first row block
            cols_ref,            # scalar prefetch: (rows * maxb,) int32
            data_row_ref,        # (8, BLOCK) row agents
            data_col_ref,        # (8, BLOCK) candidate col agents
            out_ref,             # (8, BLOCK) accumulated [fx fy fz nnz ...]
            *, maxb: int, k_rep: float, adhesion, adhesion_band: float):
    i = pl.program_id(0)
    j = pl.program_id(1)
    col_id = cols_ref[i * maxb + j]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(col_id >= 0)
    def _accum():
        row = data_row_ref[...]
        col = data_col_ref[...]
        fx, fy, fz, nnz = _force_tile(
            row, col, (base_ref[0] + i) * BLOCK, col_id * BLOCK,
            k_rep, adhesion, adhesion_band)
        # Mosaic has no in-kernel scatter: build the (8, BLOCK) update by
        # stacking whole rows, never with .at[].set
        zero = jnp.zeros_like(fx)
        out_ref[...] += jnp.stack([fx, fy, fz, nnz, zero, zero, zero, zero])


def rows_per_call(maxb: int) -> int:
    """Row blocks one ``pallas_call`` covers: its slice of the column table
    (rows × maxb int32) is the scalar-prefetched operand and must fit SMEM
    (1 MiB on v5e) at any capacity, so the table is cut into slices of at
    most ``SMEM_TABLE_ENTRIES`` entries. A width beyond that (one row block
    alone would not fit) is refused."""
    if not 1 <= maxb <= SMEM_TABLE_ENTRIES:
        raise ValueError(f"column-map width {maxb} outside [1, "
                         f"{SMEM_TABLE_ENTRIES}] (SMEM_TABLE_ENTRIES)")
    return SMEM_TABLE_ENTRIES // maxb


def padded_size(n: int, maxb: int) -> int:
    """Pool length the kernel runs on: ``n`` rounded up to whole row blocks,
    and, once the table needs more than one call, to whole calls."""
    n_rb = -(-n // BLOCK)
    per = rows_per_call(maxb)
    if n_rb > per:
        n_rb = -(-n_rb // per) * per
    return n_rb * BLOCK


def collision_force_kernel(data_t: jnp.ndarray,
                           block_cols: jnp.ndarray,
                           *, k_rep: float,
                           adhesion: Optional[Tuple[Tuple[float, ...], ...]],
                           adhesion_band: float,
                           interpret: bool) -> jnp.ndarray:
    """Run the kernel. data_t: (8, N_pad); block_cols: (N_pad/128, MAXB) int32,
    with ``N_pad == padded_size(N_pad, MAXB)``.

    Returns out_t (8, N_pad): rows [fx, fy, fz, nnz]. The column table is
    walked in slices of ``rows_per_call(MAXB)`` row blocks, one kernel
    launch per slice under ``lax.map``, so its SMEM footprint is bounded
    independently of the pool's capacity.
    """
    n_pad = data_t.shape[1]
    n_rb, maxb = block_cols.shape
    if n_rb * BLOCK != n_pad or padded_size(n_pad, maxb) != n_pad:
        raise ValueError(f"data_t has {n_pad} columns and block_cols "
                         f"{n_rb} rows; expected padded_size(...) = "
                         f"{padded_size(n_pad, maxb)} columns")
    rows = min(n_rb, rows_per_call(maxb))
    n_calls = n_rb // rows
    kern = functools.partial(_kernel, maxb=maxb, k_rep=k_rep,
                             adhesion=adhesion, adhesion_band=adhesion_band)
    call = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, maxb),
            in_specs=[
                pl.BlockSpec((8, BLOCK),
                             lambda i, j, base, cols: (0, base[0] + i)),
                pl.BlockSpec((8, BLOCK),
                             lambda i, j, base, cols:
                             (0, jnp.maximum(cols[i * maxb + j], 0))),
            ],
            out_specs=pl.BlockSpec((8, BLOCK),
                                   lambda i, j, base, cols: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((8, rows * BLOCK), jnp.float32),
        interpret=interpret,
        name="k1_collision_force",
    )
    tables = block_cols.reshape(n_calls, rows * maxb)
    bases = jnp.arange(n_calls, dtype=jnp.int32)[:, None] * rows
    outs = jax.lax.map(lambda a: call(a[0], a[1], data_t, data_t),
                       (bases, tables))                 # (calls, 8, rows·128)
    return outs.transpose(1, 0, 2).reshape(8, n_pad)
