"""Parallel agent addition/removal — paper §3.2, as prefix-sum stream compaction.

The paper parallelizes removal with swap-with-last bookkeeping (to_right /
not_to_left auxiliary arrays + prefix sums) so that holes never exist in the
ResourceManager. The TPU-native equivalent of the same idea is data-parallel
stream compaction: one ``cumsum`` over the alive mask yields every surviving
agent's destination slot, and a scatter moves all channels at once. Work is
O(capacity) fully parallel (the paper's is O(removed) on a PRAM; under SPMD/XLA
the masked full-width scan is the faster realization because it is a single
vectorized pass with no data-dependent control flow).

Additions mirror the paper's thread-local queues: behaviors stage newborn agents
in a fixed-capacity *birth queue*; the commit reserves contiguous slots at the
tail ``[n_live, n_live + n_new)`` via the same prefix sum.

The per-step resident reorder (grid.build_resident) routes through
:func:`apply_permutation` with the grid sort key's argsort: dead slots carry
the maximum key (morton.DEAD_KEY), so the one permutation simultaneously
grid-orders the live agents and compacts the dead to the tail — composing the
paper's §3.2 removal with its §4.2 memory-layout sort.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .agents import AgentPool


def compaction_permutation(alive: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Permutation placing live slots first (stable), dead after (stable).

    Returns (perm, n_live): ``new[i] = old[perm[i]]``.
    """
    c = alive.shape[0]
    alive_i = alive.astype(jnp.int32)
    n_live = jnp.sum(alive_i)
    # destination of each old slot
    dst_live = jnp.cumsum(alive_i) - 1                      # valid where alive
    dst_dead = n_live + jnp.cumsum(1 - alive_i) - 1         # valid where dead
    dst = jnp.where(alive, dst_live, dst_dead)              # (C,) a permutation
    # invert: perm[dst[i]] = i
    perm = jnp.zeros((c,), jnp.int32).at[dst].set(jnp.arange(c, dtype=jnp.int32))
    return perm, n_live


def apply_permutation(pool: AgentPool, perm: jnp.ndarray) -> AgentPool:
    """Gather-reorder every SoA channel by ``perm``."""
    ch = pool.channels()
    return pool.with_channels({k: jnp.take(v, perm, axis=0) for k, v in ch.items()})


def compact(pool: AgentPool) -> AgentPool:
    """Remove dead agents: live agents move (stably) to slots [0, n_live)."""
    perm, _ = compaction_permutation(pool.alive)
    return apply_permutation(pool, perm)


def commit_births(pool: AgentPool, queue: Dict[str, jnp.ndarray],
                  queue_valid: jnp.ndarray, iteration: jnp.ndarray) -> AgentPool:
    """Append staged newborn agents at the tail of the live region.

    queue: dict of (Q, ...) channel arrays (same channel names as the pool,
           missing channels default to zeros / sensible flags).
    queue_valid: (Q,) bool — which queue slots hold a real newborn.
    Newborns whose destination exceeds capacity are dropped (counted by the
    engine as overflow; capacity sizing is a config responsibility).

    Queue-provided channels always win over the defaults below — which is
    what lets the distributed engine append migration *arrivals* through
    this same path (DESIGN.md §7.2): a migrating agent ships every channel
    (born_iter, moved/grew bookkeeping, behavior extras, owned flag) and
    lands on the destination shard bit-identical, including agents that were
    themselves born earlier in the same iteration.
    """
    c = pool.capacity
    n_live = pool.n_live
    qv = queue_valid.astype(jnp.int32)
    dst = n_live + jnp.cumsum(qv) - 1                      # (Q,) destination slots
    ok = queue_valid & (dst < c)
    dst = jnp.where(ok, dst, c)                            # parked writes go to c (dropped)

    ch = pool.channels()
    out = {}
    for k, v in ch.items():
        if k in queue:
            src = queue[k]
        elif k == "alive":
            src = jnp.ones(queue_valid.shape, bool)
        elif k == "static":
            src = jnp.zeros(queue_valid.shape, bool)
        elif k == "moved":
            src = jnp.ones(queue_valid.shape, bool)        # newborns wake neighborhoods
        elif k == "grew":
            src = jnp.ones(queue_valid.shape, bool)
        elif k == "born_iter":
            src = jnp.full(queue_valid.shape, iteration, jnp.int32)
        elif k == "force_nnz":
            src = jnp.zeros(queue_valid.shape, jnp.int32)
        else:
            src = jnp.zeros(queue_valid.shape + v.shape[1:], v.dtype)
        # scatter with drop semantics for parked index c
        out[k] = v.at[dst].set(src.astype(v.dtype), mode="drop")
    return pool.with_channels(out)


def birth_overflow(pool: AgentPool, queue_valid: jnp.ndarray) -> jnp.ndarray:
    """Number of staged newborns that will not fit in capacity."""
    n_new = jnp.sum(queue_valid.astype(jnp.int32))
    free = pool.capacity - pool.n_live
    return jnp.maximum(n_new - free, 0)


# ---------------------------------------------------------------------------
# Capacity-ladder restage (DESIGN.md §4.3)
# ---------------------------------------------------------------------------
#
# Growing a rung cannot resize arrays in place (XLA shapes are static): the
# restage allocates the larger fixed-shape channels and copies the old pool
# into the prefix. The old buffers are *donated* — XLA may reuse their memory
# for the output, so peak footprint during a grow is new + O(1) channels, not
# old + new. (Donation is a no-op on backends that don't implement it, e.g.
# CPU; correctness never depends on it.)

_GROW_CACHE: dict = {}


def _grow_fn(new_capacity: int, donate: bool):
    key = (new_capacity, donate)
    if key not in _GROW_CACHE:
        def grow(ch: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
            out = {}
            for k, v in ch.items():
                pad = jnp.zeros((new_capacity - v.shape[0], *v.shape[1:]),
                                v.dtype)
                out[k] = jnp.concatenate([v, pad], axis=0)
            return out
        _GROW_CACHE[key] = jax.jit(grow, donate_argnums=(0,) if donate else ())
    return _GROW_CACHE[key]


def grow_channels(ch: Dict[str, jnp.ndarray], new_capacity: int,
                  donate: bool | None = None) -> Dict[str, jnp.ndarray]:
    """Re-stage a channel dict into ``new_capacity`` slots (dtype-preserving).

    Slots ``[old_capacity, new_capacity)`` are zero-filled — dead (``alive``
    False), exactly like the tail of a freshly made pool — so live-trajectory
    parity vs a pre-sized pool holds (dead-slot content never reaches a live
    agent; DESIGN.md §4.3). ``donate`` defaults to on wherever the backend
    implements buffer donation.
    """
    cap = next(iter(ch.values())).shape[0]
    if new_capacity < cap:
        raise ValueError(f"cannot shrink pool {cap} -> {new_capacity}")
    if new_capacity == cap:
        return ch
    if donate is None:
        donate = jax.default_backend() not in ("cpu",)
    return _grow_fn(new_capacity, donate)(ch)


def grow_pool(pool: AgentPool, new_capacity: int,
              donate: bool | None = None) -> AgentPool:
    """Re-stage a pool into a larger fixed-shape pool (capacity-ladder rung)."""
    return pool.with_channels(grow_channels(pool.channels(), new_capacity,
                                            donate))


def repack_slabs(channels: Dict[str, jnp.ndarray], n_shards: int,
                 old_local: int, new_local: int) -> Dict[str, jnp.ndarray]:
    """Host-side re-pack of sharded slab channels into a new local width.

    Channels are global ``(n_shards·old_local, ...)`` arrays with shard i's
    agents in slice ``[i·old_local, i·old_local + n_i)``. Each shard's slab is
    preserved verbatim and padded with zero (dead) tail slots — the
    distributed analog of :func:`grow_channels`. Shared by the distributed
    capacity ladder's rung restage and checkpoint restore onto a run whose
    ``local_capacity`` rung differs (core/simcheck.py).
    """
    if new_local < old_local:
        raise ValueError(f"cannot shrink slabs {old_local} -> {new_local}")
    out = {}
    for k, v in channels.items():
        a = np.asarray(v).reshape((n_shards, old_local) + v.shape[1:])
        pad = np.zeros((n_shards, new_local - old_local) + v.shape[1:],
                       a.dtype)
        out[k] = jnp.asarray(
            np.concatenate([a, pad], axis=1).reshape(
                (n_shards * new_local,) + v.shape[1:]))
    return out


def active_index_list(active: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compact the indices of active agents to the front (static-region support).

    Returns (idx, n_active): ``idx[:n_active]`` are the active slots in order,
    the tail is padded with the last active index (safe to compute, ignored).
    Used to run the force computation over ⌈n_active/B⌉ blocks only (§5 / O6).
    """
    c = active.shape[0]
    a = active.astype(jnp.int32)
    n_active = jnp.sum(a)
    dst = jnp.where(active, jnp.cumsum(a) - 1, c)          # parked for inactive
    idx = jnp.zeros((c,), jnp.int32).at[dst].set(
        jnp.arange(c, dtype=jnp.int32), mode="drop")
    # pad the tail with a safe index (0 if none active)
    pad_val = jnp.where(n_active > 0, idx[jnp.maximum(n_active - 1, 0)], 0)
    idx = jnp.where(jnp.arange(c) < n_active, idx, pad_val)
    return idx, n_active


def active_block_list(active: jnp.ndarray, block: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ids of ``block``-sized slot ranges containing ≥1 active agent.

    The block-granular form of :func:`active_index_list` (paper §5 / O6 on a
    vector machine): the resident layout keeps queries contiguous, so the
    force loop slices whole blocks and skips fully-inactive ones outright via
    a dynamic trip count. ``active.shape[0]`` need not divide ``block``; the
    trailing partial range counts as one block. Returns (blk_idx, n_blocks)
    with the tail of ``blk_idx`` padded safely (see active_index_list).
    """
    return active_index_list(active_blocks(active, block))


def active_blocks(active: jnp.ndarray, block: int) -> jnp.ndarray:
    """(⌈C/block⌉,) bool: which ``block``-sized slot ranges hold ≥1 active
    agent (the trailing partial range is one)."""
    c = active.shape[0]
    n_blk = (c + block - 1) // block
    pad = n_blk * block - c
    return jnp.any(jnp.pad(active, (0, pad)).reshape(n_blk, block), axis=1)
