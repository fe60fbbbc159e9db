"""Brute-force pair sweeps in blocks, so that a reference fits the device.

Every query is paired with every candidate: no grid, no sort, no kernel of
the engine. Queries are cut into blocks (``lax.map``) and candidates into
chunks (``lax.scan``), so the largest temporary is one block by one chunk.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import numpy as np


def bucket(n: int, quantum: int) -> int:
    """``n`` rounded up to a multiple of ``quantum`` (at least one): padded
    lengths that repeat from run to run, so the sweep compiles once."""
    return max(1, -(-n // quantum)) * quantum


def _pad(arrays: Dict[str, np.ndarray], length: int, fill: Dict[str, float]):
    out = {}
    for k, v in arrays.items():
        v = np.asarray(v)
        pad = np.full((length - v.shape[0],) + v.shape[1:], fill.get(k, 0),
                      v.dtype)
        out[k] = np.concatenate([v, pad])
    return out


def sweep(queries: Dict[str, np.ndarray], cands: Dict[str, np.ndarray],
          init: Callable, step: Callable, params, *, q_block: int = 128,
          c_block: int = 8192, c_quantum: int = 1 << 17,
          fill: Dict[str, float] | None = None):
    """Fold ``step(carry, q, c, params) -> carry`` over all candidate
    chunks, for every block of queries; returns the per-query carry (numpy).

    ``q`` and ``c`` are dicts of (block,) / (chunk,) arrays; ``c["valid"]``
    is False on padding. ``init(q, params)`` gives the per-block start
    carry. ``init``, ``step`` and ``params`` are static (module-level
    functions and a hashable tuple), so each sweep compiles once.
    Candidates are padded with ``fill`` values to a multiple of
    ``c_quantum`` (a multiple of ``c_block``), or below it to a power of
    two, so runs whose counts differ a little share one compiled sweep.
    """
    fill = fill or {}
    nq = next(iter(queries.values())).shape[0]
    nc = next(iter(cands.values())).shape[0]
    qlen = bucket(nq, q_block)
    if nc > c_quantum:
        clen = bucket(nc, c_quantum)
    else:                              # few candidates: a power of two
        clen = max(1024, 1 << (nc - 1).bit_length())
        c_block = min(c_block, clen)
    q = _pad(queries, qlen, fill)
    c = _pad(dict(cands, valid=np.ones(nc, bool)), clen, fill)
    q = {k: v.reshape((-1, q_block) + v.shape[1:]) for k, v in q.items()}
    c = {k: v.reshape((-1, c_block) + v.shape[1:]) for k, v in c.items()}
    out = _run(init, step, params, q, c)
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x).reshape((-1,) + x.shape[2:])[:nq], out)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _run(init, step, params, q, c):
    def per_block(qb):
        def body(carry, cb):
            return step(carry, qb, cb, params), None
        carry, _ = jax.lax.scan(body, init(qb, params), c)
        return carry
    return jax.lax.map(per_block, q)
