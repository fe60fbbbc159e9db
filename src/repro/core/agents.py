"""Agent pool — fixed-capacity SoA storage (paper ResourceManager + §4.3 pool allocator).

BioDynaMo's ResourceManager stores raw agent pointers per NUMA domain and its
pool allocator hands out fixed-size elements from preallocated blocks. Under
jit, XLA forbids dynamic allocation entirely, so the TPU-native endpoint of the
paper's idea is a *fully preallocated* structure-of-arrays pool with an ``alive``
mask: dead slots are the free list, and 'allocation' is slot reservation via a
prefix sum (compaction.py). One XLA program serves the whole simulation.

Invariant maintained by the engine (mirrors the paper's "disallow empty vector
elements in the ResourceManager"): live agents occupy slots ``[0, n_live)``;
slots ``[n_live, capacity)`` are free. This makes per-device partitioning and
the windowed force kernel's index math trivial.

Under the resident grid layout (grid.build_resident, DESIGN.md §3.2) the
engine strengthens this at every grid build: live agents sit in [0, n_live)
*in row-major grid-key order* — agents of the same box are adjacent, boxes
are adjacent along z. Slot ids are therefore stable only within an iteration;
anything tracking agents across steps must key on channel state, not slot
index (the permutation is returned by build_resident for callers that need
to re-map).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """Per-channel storage dtypes — each capacity rung holds more agents/byte.

    Positions stay float32 unconditionally (force accuracy and grid keys
    depend on them); the policy only narrows *auxiliary* channels:

      aux_float:    dtype name for ``diameter`` and every float32 behavior
                    extra channel ('float32' | 'bfloat16' | 'float16').
                    Narrowing is a tolerance trade, not bit-exact — the
                    ladder parity contract is float32-policy only.
      compact_ints: store ``agent_type`` and ``force_nnz`` as int16.
                    Range-safe when type ids < 32768 and an agent's neighbor
                    count < 32768 (both hold for every paper scenario);
                    ``born_iter`` stays int32 (iteration counts don't fit).

    Strings (not dtypes) keep the policy hashable inside the frozen
    EngineConfig jit cache key.
    """

    aux_float: str = "float32"
    compact_ints: bool = False

    @property
    def aux_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.aux_float)

    @property
    def int_dtype(self) -> jnp.dtype:
        return jnp.dtype(jnp.int16 if self.compact_ints else jnp.int32)

    def extra_dtype(self, declared: Any) -> jnp.dtype:
        """Storage dtype for a behavior extra channel declared as ``declared``."""
        if jnp.dtype(declared) == jnp.dtype(jnp.float32):
            return self.aux_dtype
        return jnp.dtype(declared)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AgentPool:
    """Structure-of-arrays agent storage. All arrays have leading dim = capacity.

    Fields:
      position:   (C, 3) float — agent center.
      diameter:   (C,)   float — sphere diameter.
      agent_type: (C,)   int32 — user-defined type id (e.g. cell type, SIR state).
      alive:      (C,)   bool  — live mask; live agents are compacted to the front.
      static:     (C,)   bool  — static-region flag (paper §5); static agents skip
                                 the pairwise force computation.
      moved:      (C,)   bool  — condition (i) bookkeeping: displaced last iteration.
      grew:       (C,)   bool  — condition (ii): force-relevant attribute increased.
      born_iter:  (C,)   int32 — iteration of creation (condition (iii) support).
      force_nnz:  (C,)   int32 — count of non-zero neighbor forces last iteration
                                 (condition (iv)).
      extra:      dict of (C, ...) arrays — per-behavior state channels
                  (e.g. infection timer, growth rate, neurite direction).
    """

    position: jnp.ndarray
    diameter: jnp.ndarray
    agent_type: jnp.ndarray
    alive: jnp.ndarray
    static: jnp.ndarray
    moved: jnp.ndarray
    grew: jnp.ndarray
    born_iter: jnp.ndarray
    force_nnz: jnp.ndarray
    extra: Dict[str, jnp.ndarray]

    @property
    def capacity(self) -> int:
        return self.position.shape[0]

    @property
    def n_live(self) -> jnp.ndarray:
        """Number of live agents (traced scalar)."""
        return jnp.sum(self.alive.astype(jnp.int32))

    def channels(self) -> Dict[str, jnp.ndarray]:
        """Flat view of every per-agent channel (for reorder/compaction)."""
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self) if f.name != "extra"}
        for k, v in self.extra.items():
            out["extra." + k] = v
        return out

    def with_channels(self, ch: Dict[str, jnp.ndarray]) -> "AgentPool":
        return pool_from_channels(ch)


def pool_from_channels(ch: Dict[str, jnp.ndarray]) -> AgentPool:
    """Rebuild a pool from a flat channel dict (inverse of ``channels()``).

    The channel-name set *is* the pool's spec: the distributed engine derives
    its ghost/migration buffer layout from it (DESIGN.md §7), so behaviors'
    extra channels automatically cross shard boundaries.
    """
    base = {k: v for k, v in ch.items() if not k.startswith("extra.")}
    extra = {k[len("extra."):]: v for k, v in ch.items()
             if k.startswith("extra.")}
    return AgentPool(extra=extra, **base)


def make_pool(capacity: int,
              n_live: int = 0,
              position: jnp.ndarray | None = None,
              diameter: jnp.ndarray | None = None,
              agent_type: jnp.ndarray | None = None,
              extra_specs: Dict[str, Any] | None = None,
              dtype: jnp.dtype = jnp.float32,
              policy: DtypePolicy | None = None) -> AgentPool:
    """Allocate a pool of ``capacity`` slots; fill the first ``n_live`` from args.

    ``extra_specs`` maps channel name → (shape_suffix, dtype, fill_value) or an
    (n_live, ...) array of initial values; a callable ``fill_value`` is given
    the (capacity,) slot indices and returns each slot's value. ``policy``
    narrows auxiliary channel dtypes (DtypePolicy); positions keep ``dtype``
    (float32) regardless.
    """
    policy = policy or DtypePolicy()
    if position is not None:
        n_live = position.shape[0]

    def pad(arr, fill, shape_suffix=(), dt=None):
        dt = dt or (arr.dtype if arr is not None else dtype)
        full = jnp.full((capacity, *shape_suffix), fill, dtype=dt)
        if arr is not None and n_live > 0:
            full = full.at[:n_live].set(arr.astype(dt))
        return full

    pos = pad(position, 0.0, (3,), dtype)
    dia = pad(diameter, 0.0, (), policy.aux_dtype) if diameter is not None \
        else pad(None, 10.0, (), policy.aux_dtype)
    if diameter is None and n_live > 0:
        dia = dia.at[:n_live].set(10.0)
    typ = pad(agent_type, 0, (), policy.int_dtype) if agent_type is not None \
        else jnp.zeros((capacity,), policy.int_dtype)
    alive = jnp.arange(capacity) < n_live

    extra = {}
    for name, spec in (extra_specs or {}).items():
        if isinstance(spec, tuple):
            shape_suffix, dt, fill = spec
            dt = policy.extra_dtype(dt)
            if callable(fill):
                values = fill(jnp.arange(capacity, dtype=jnp.int32))
                extra[name] = jnp.broadcast_to(
                    jnp.reshape(values, (capacity,) + (1,) * len(shape_suffix)),
                    (capacity, *shape_suffix)).astype(dt)
            else:
                extra[name] = jnp.full((capacity, *shape_suffix), fill,
                                       dtype=dt)
        else:  # array of initial live values
            arr = jnp.asarray(spec)
            dt = policy.extra_dtype(arr.dtype)
            full = jnp.zeros((capacity, *arr.shape[1:]), dtype=dt)
            extra[name] = full.at[:n_live].set(arr.astype(dt))

    return AgentPool(
        position=pos,
        diameter=dia,
        agent_type=typ,
        alive=alive,
        static=jnp.zeros((capacity,), bool),
        moved=jnp.ones((capacity,), bool),   # everything "moved" at t=0: no static skips
        grew=jnp.zeros((capacity,), bool),
        born_iter=jnp.zeros((capacity,), jnp.int32),
        force_nnz=jnp.zeros((capacity,), policy.int_dtype),
        extra=extra,
    )
