"""Plain reference of an SIR epidemic on a random walk (epidemiology).

What one step does to a live agent i, written from the model (Kermack and
McKendrick's SIR over spatial contacts), not from the engine's code:

  exposed    some other infected agent j has |x_j − x_i|² ≤ r²  (inclusive)
  S          exposed: becomes I with probability β (timer := recovery),
             or stays S; not exposed: stays S, timer unchanged
  I          timer − 1; becomes R when the timer reaches 0
  R          unchanged
  walk       x += sigma dt z per axis, z standard normal, clipped to the domain
  identity   every agent is kept: none is born, none dies

The infection draw and the walk are random, so they are judged by their
statistics over all agents, as z-scores that do not depend on the
population's size:

  infect_z   new infections against Binomial(|exposed S|, beta)
  walk_z     per axis, the mean squared displacement of the agents at least
             8 sigma dt from both walls against (sigma dt)^2, whose standard
             error over N Gaussian steps is (sigma dt)^2 sqrt(2 / N)

A sweep that misses neighbours lowers ``infect_z``'s count; a walk skipped,
doubled or applied to the wrong agent moves ``walk_z``. Every agent carries
its identity in its diameter (deploy.py, ``tag_diameter``), which this
deployment's dynamics never read. Exposure is decided for every agent
against every infected agent, by brute force in blocks (blocks.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from bench.reference import blocks

SUSCEPTIBLE, INFECTED, RECOVERED = 0, 1, 2
SLACK = 1e-5        # relative band on r²: pairs this close to the radius
                    # count as exposed whichever way rounding decides them
WALL = 8.0          # walk steps from a wall beyond which no clip occurs
NONE = 1e9          # a z-score where nothing is there to be judged


class Contact(NamedTuple):
    r2: float
    dtype: str


def _behavior(params: dict, name: str) -> dict:
    return next(b for b in params["behaviors"] if b["class"] == name)


def _init(q, k):
    return jnp.zeros(q["x"].shape, jnp.int32)


def _step(count, q, c, k):
    dt = jnp.dtype(k.dtype)
    d2 = sum((c[a].astype(dt)[None, :] - q[a].astype(dt)[:, None]) ** 2
             for a in ("x", "y", "z"))
    hit = c["valid"][None, :] & (q["i"][:, None] != c["i"][None, :]) \
        & (d2 <= k.r2)
    return count + jnp.sum(hit, axis=1, dtype=jnp.int32)


def exposure(before: dict, radius: float, dtype: str = "float32",
             slack: float = 0.0) -> np.ndarray:
    """(N,) bool: agents with an infected agent within ``radius``."""
    pos, types = before["position"], before["agent_type"]
    idx = np.arange(len(types), dtype=np.int32)
    inf = types == INFECTED
    q = {"x": pos[:, 0], "y": pos[:, 1], "z": pos[:, 2], "i": idx}
    c = {"x": pos[inf, 0], "y": pos[inf, 1], "z": pos[inf, 2], "i": idx[inf]}
    if not inf.any():
        return np.zeros(len(types), bool)
    count = blocks.sweep(q, c, _init, _step,
                         Contact(radius * radius * (1.0 + slack), dtype),
                         q_block=1024, fill={"i": -1})
    return count > 0


def identity(diameter: np.ndarray, base: float) -> np.ndarray:
    """The agent ids carried by ``tag_diameter`` diameters."""
    return np.rint((diameter.astype(np.float64) / base - 1.0)
                   * 2.0 ** 23).astype(np.int64)


def compare(before: dict, after: dict, params: dict) -> dict:
    """``bad_share``: the share of agents whose state after the step is
    impossible under the reference (also an agent lost or duplicated);
    ``infect_z`` and ``walk_z`` (module docstring)."""
    inf = _behavior(params, "Infection")
    base = params["population"]["diameter"][0]
    exposed = exposure(before, inf["radius"], slack=SLACK)
    ids_in = identity(before["diameter"], base)
    ids_out = identity(after["diameter"], base)
    order = np.argsort(ids_out, kind="stable")
    sorted_out = ids_out[order]
    at = np.clip(np.searchsorted(sorted_out, ids_in), 0, len(sorted_out) - 1)
    present = sorted_out[at] == ids_in
    unique = present & (np.searchsorted(sorted_out, ids_in, side="right")
                        - np.searchsorted(sorted_out, ids_in) == 1)
    j = order[at]
    t, tm = before["agent_type"], before["extra.infect_timer"]
    t2, tm2 = after["agent_type"][j], after["extra.infect_timer"][j]
    rec = inf["recovery_time"]
    tm_dec = tm - 1
    ok = np.where(
        t == SUSCEPTIBLE,
        ((t2 == SUSCEPTIBLE) & (tm2 == tm))
        | (exposed & (t2 == INFECTED) & (tm2 == rec)),
        np.where(t == INFECTED,
                 (tm2 == tm_dec)
                 & (t2 == np.where(tm_dec <= 0, RECOVERED, INFECTED)),
                 (t2 == t) & (tm2 == tm)))
    ok &= unique
    extra = len(ids_out) - int(unique.sum())
    bad = int((~ok).sum()) + max(extra, 0)
    return {"bad_share": bad / max(len(ids_in), 1),
            "infect_z": infect_z(t == SUSCEPTIBLE, exposed, unique, t2,
                                 inf["beta"]),
            "walk_z": walk_z(before["position"][unique],
                             after["position"][j[unique]], params)}


def infect_z(susceptible, exposed, unique, t2, beta: float) -> float:
    """|new infections − beta E| over the binomial standard deviation, E the
    exposed susceptibles."""
    e = int((susceptible & exposed).sum())
    new = int((susceptible & unique & (t2 == INFECTED)).sum())
    if e == 0:
        return 0.0 if new == 0 else NONE
    return abs(new - beta * e) / float(np.sqrt(e * beta * (1.0 - beta)))


def walk_z(x0: np.ndarray, x1: np.ndarray, params: dict) -> float:
    """The worst axis's |MSD − s²| over its standard error, s = sigma dt,
    over the agents no wall can have clipped."""
    s = _behavior(params, "RandomWalk")["sigma"] * params["engine"]["dt"]
    lo, hi = (np.asarray(b, np.float64) for b in params["domain"])
    x0, x1 = x0.astype(np.float64), x1.astype(np.float64)
    worst = 0.0
    for a in range(3):
        inner = (x0[:, a] > lo[a] + WALL * s) & (x0[:, a] < hi[a] - WALL * s)
        n = int(inner.sum())
        if n == 0:
            return NONE
        msd = float(np.mean((x1[inner, a] - x0[inner, a]) ** 2))
        err = s * s * float(np.sqrt(2.0 / n))
        worst = max(worst, abs(msd - s * s) / err)
    return worst


def control(before: dict, params: dict, seed: int) -> dict:
    """Outputs of the reference put in the program's place, one precision
    below the configuration's float32: exposure and the walk in bfloat16,
    the draws from ``seed``."""
    inf = _behavior(params, "Infection")
    exposed = exposure(before, inf["radius"], "bfloat16")
    t, tm = before["agent_type"], before["extra.infect_timer"]
    rng = np.random.default_rng(seed)
    u = rng.random(len(t))
    newly = (t == SUSCEPTIBLE) & exposed & (u < inf["beta"])
    is_inf = t == INFECTED
    tm2 = np.where(newly, inf["recovery_time"], np.where(is_inf, tm - 1, tm))
    t2 = np.where(newly, INFECTED,
                  np.where(is_inf & (tm2 <= 0), RECOVERED, t))
    s = _behavior(params, "RandomWalk")["sigma"] * params["engine"]["dt"]
    bf = jnp.bfloat16
    step = jnp.asarray(s * rng.standard_normal(before["position"].shape), bf)
    lo, hi = (jnp.asarray(b, bf) for b in params["domain"])
    pos = jnp.clip(jnp.asarray(before["position"], bf) + step, lo, hi)
    return {"diameter": before["diameter"], "agent_type": t2,
            "extra.infect_timer": tm2,
            "position": np.asarray(pos.astype(jnp.float32))}


def numbers(before: dict, after: dict, params: dict, sample, chk: dict
            ) -> dict:
    """The cell's compared numbers for one step (every agent is checked)."""
    return compare(before, after, params)


def control_numbers(before: dict, params: dict, sample, chk: dict,
                    seed: int) -> dict:
    """The same numbers read from the control (``control``)."""
    return compare(before, control(before, params, seed), params)
