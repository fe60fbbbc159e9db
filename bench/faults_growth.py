"""Faults of the growing spheroid's timed path, each of which its check has
to catch (``correct`` false): the contact forces, K1's column map, division,
death and the walk, beside ``bench/faults.py``'s faults of the step itself.

``planted`` works as ``faults.planted`` does: a ``Simulation`` built inside
picks the fault up. ``bench/test_contact_growth.py`` runs every fault of the
cell through a whole CPU run; ``bench/control_growth.py --fault`` reads one
on the chip at the cell's own size.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from bench import faults
from repro.core import behaviors, forces
from repro.kernels import ops


def _forces_skipped(displacement):
    """Every cell's contact force is taken as zero."""
    def skipped(force, params, dt):
        return jnp.zeros_like(displacement(force, params, dt))
    return skipped


def _colmap_halved(build):
    """K1's column map keeps the first half of each row block's column
    blocks, and says nothing."""
    def halved(*args, **kw):
        cols, ovf, demand = build(*args, **kw)
        count = jnp.sum(cols >= 0, axis=1, keepdims=True)
        keep = jnp.arange(cols.shape[1])[None, :] < (count + 1) // 2
        return jnp.where(keep, cols, -1), ovf, demand
    return halved


def _divisions_skipped(call):
    """Cells grow past the threshold and never divide."""
    def __call__(self, ctx, pool, rng):
        grown = pool.diameter + behaviors.resolve(self.rate, ctx) * ctx.dt
        return behaviors.BehaviorEffects(set_channels={
            "diameter": jnp.where(self._mask(ctx, pool), grown,
                                  pool.diameter)})
    return __call__


def _deaths_skipped(call):
    """No cell dies."""
    def __call__(self, ctx, pool, rng):
        return behaviors.BehaviorEffects()
    return __call__


FAULTS = {
    "forces_skipped": (forces, "displacement", _forces_skipped),
    "colmap_halved": (ops, "build_block_cols", _colmap_halved),
    "divisions_skipped": (behaviors.GrowDivide, "__call__",
                          _divisions_skipped),
    "deaths_skipped": (behaviors.RandomDeath, "__call__", _deaths_skipped),
    "walk_skipped": faults.FAULTS["walk_skipped"],
    "half_left_out": faults.FAULTS["half_left_out"],
}

# the faults each cell can have
CELLS = {"oncology-growth": tuple(FAULTS)}


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted, restored on exit."""
    owner, attr, wrap = FAULTS[name]
    original = owner.__dict__[attr]
    setattr(owner, attr, wrap(original))
    try:
        yield
    finally:
        setattr(owner, attr, original)
