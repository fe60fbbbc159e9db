"""The work K1 (``repro.kernels.collision_force``) cannot avoid, and the
peaks of the chips it runs on.

K1 visits one 128×128 tile of pairs for each entry of its column map
(``StepStats.k1_tiles``). For each pair it computes at least
``OPS_PER_PAIR`` vector operations (counted below, each a full-width op on
the VPU, square roots and the power included); it reads each visited
column block's (8, 128) float32 operand, and each live row block's operand
once in and its output once out. The least time for a count of tiles is
the larger of the operations over the VPU's peak and the bytes over HBM's.
"""

from __future__ import annotations

KERNEL = "k1_collision_force"  # the pallas_call's name, in its op_name
BLOCK = 128                    # rows and columns of a tile
TILE_BYTES = 8 * BLOCK * 4     # one (8, 128) float32 operand

# Per pair, of the kernel's own arithmetic (collision_force._force_tile,
# repulsion only): the offset and squared distance (3 + 3 + 2), its floor
# and square root (2), the overlap (2), the effective radius (5), the Hertz
# magnitude: a square root, a floor, the power and two products (5), the
# pair's mask (3), the select (1), the reciprocal distance (1), the three
# projections and their sums (6 + 3), the nonzero count (2). The kernel's
# own program has more (tests keep this at or below it).
OPS_PER_PAIR = 38

# device_kind -> peaks. TPU v5e ("TPU v5 lite"): HBM 819 GB/s (Google Cloud
# documentation, "TPU v5e"). VPU: no figure is published. The clock comes
# from the published 197 TFLOP/s bf16 of its four 128x128 MXUs:
# 197e12 / (4 * 128 * 128 * 2) = 1.503 GHz. The width, an (8, 128) float32
# vector register, is Pallas's native tile. VPU_SLOTS, the vector ALU
# operations issued a cycle, is an ASSUMPTION, not a published number: the
# peak is 4 * 8 * 128 * 1.503e9 = 6.16e12 float32 ops/s. A chip that issues
# more a cycle has a higher peak, and the true share is then lower than
# the one read here by the same factor.
_CLOCK_HZ = 197e12 / (4 * 128 * 128 * 2)
VPU_SLOTS = 4
PEAKS = {
    "TPU v5 lite": {"vpu_ops_per_s": VPU_SLOTS * 8 * 128 * _CLOCK_HZ,
                    "hbm_bytes_per_s": 819e9},
}


def operations(tiles: int) -> float:
    return float(tiles) * BLOCK * BLOCK * OPS_PER_PAIR


def bytes_moved(tiles: int, live_rows: int) -> float:
    row_blocks = -(-int(live_rows) // BLOCK)
    return float(tiles) * TILE_BYTES + 2.0 * row_blocks * TILE_BYTES


def least_seconds(tiles: int, live_rows: int, device_kind: str) -> float:
    """The larger of the compute and the memory bound; a device the table
    does not hold is an error."""
    peak = PEAKS[device_kind]
    return max(operations(tiles) / peak["vpu_ops_per_s"],
               bytes_moved(tiles, live_rows) / peak["hbm_bytes_per_s"])


def bound(tiles: int, live_rows: int, device_kind: str) -> str:
    """Which of the two bounds sets ``least_seconds``."""
    peak = PEAKS[device_kind]
    compute = operations(tiles) / peak["vpu_ops_per_s"]
    memory = bytes_moved(tiles, live_rows) / peak["hbm_bytes_per_s"]
    return "compute" if compute >= memory else "memory"
