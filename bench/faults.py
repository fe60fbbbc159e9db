"""Faults planted in the timed path, each of which a cell's check has to
catch (``correct`` false).

Each fault replaces one attribute of the program with a broken wrapper of
the original, for as long as ``planted`` holds it; a ``Simulation`` built
inside picks it up. ``bench/test_faults.py`` runs every fault a cell can
have through a whole CPU run; ``bench/control.py --fault`` reads one on the
chip at the cell's own size. One chip, so no exchange between chips to
leave out.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp

from repro.core import behaviors, engine


def _unchanged(run):
    """A step that returns its state unchanged."""
    def step(self, state, n, **kw):
        return dataclasses.replace(state, iteration=state.iteration + n)
    return step


def _half_left_out(run):
    """Half of the agents left out of the step."""
    def step(self, state, n, **kw):
        pool = state.pool
        keep = pool.alive & (jnp.arange(pool.capacity) % 2 == 0)
        state = dataclasses.replace(
            state, pool=dataclasses.replace(pool, alive=keep))
        return run(self, state, n, **kw)
    return step


def _answer_altered(run):
    """One infected agent's recovery timer one step late."""
    def step(self, state, n, **kw):
        out = run(self, state, n, **kw)
        pool = out.pool
        i = jnp.argmax(pool.agent_type == 1)
        extra = dict(pool.extra,
                     infect_timer=pool.extra["infect_timer"].at[i].add(1))
        return dataclasses.replace(
            out, pool=dataclasses.replace(pool, extra=extra))
    return step


def _sweep_finds_nothing(make_pair_fn):
    """The Infection sweep reports no infected neighbour to anyone."""
    def _pair_fn(self):
        pair_fn = make_pair_fn(self)

        def broken(q, nbr, valid, q_slot):
            return {k: jnp.zeros_like(v)
                    for k, v in pair_fn(q, nbr, valid, q_slot).items()}
        return broken
    return _pair_fn


def _walk_skipped(call):
    """The random walk leaves every agent where it was."""
    def __call__(self, ctx, pool, rng):
        return behaviors.BehaviorEffects(
            set_channels={"position": pool.position})
    return __call__


FAULTS = {
    "unchanged": (engine.Simulation, "run", _unchanged),
    "half_left_out": (engine.Simulation, "run", _half_left_out),
    "answer_altered": (engine.Simulation, "run", _answer_altered),
    "sweep_finds_nothing": (behaviors.Infection, "_pair_fn",
                            _sweep_finds_nothing),
    "walk_skipped": (behaviors.RandomWalk, "__call__", _walk_skipped),
}

# the faults each cell can have
CELLS = {
    "epidemiology-sir": ("unchanged", "half_left_out", "answer_altered",
                         "sweep_finds_nothing", "walk_skipped"),
}


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted, restored on exit."""
    cls, attr, wrap = FAULTS[name]
    original = cls.__dict__[attr]
    setattr(cls, attr, wrap(original))
    try:
        yield
    finally:
        setattr(cls, attr, original)
