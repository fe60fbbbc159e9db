"""Plain reference of a growing, dividing, dying tumour spheroid (oncology).

What one step does to the live cells, written from the model (BioDynaMo's
oncology use case: Cortex3D contact forces, growth and division, random
death and a random walk), not from the engine's code:

  force      on each cell i, the sum over every other cell j of the Hertz
             repulsion  F = k √r_eff max(δ, 0)^1.5  along j → i, with
             δ = r_i + r_j − |x_j − x_i| and r_eff = r_i r_j / (r_i + r_j)
  move       x += F dt / ζ, cut to a length of at most max_displacement,
             clipped to the domain
  grow       d += rate dt; a cell whose grown diameter reaches the threshold
             divides: its diameter becomes d 2^(-1/3) (half the volume),
             and a daughter of that diameter appears at distance d/2 from
             the moved mother, with the mother's clone label and type
  walk       x += sigma dt z per axis, z standard normal (the mother after
             division, never the daughter of this step), clipped
  death      each cell that entered the step dies with probability p; a
             daughter of this step does not, and a cell that divides and
             dies leaves its daughter

Identity: each cell carries its seed's clone label (``extra.clone``, the
Lineage behavior), which daughters inherit, and the step it was born
(``born_iter``). Within a clone, a cell that entered the step is found again
within max_displacement + the walk's band of where it was, and a daughter of
this step at d/2 ± max_displacement from where its mother was: the two bands
are disjoint while d > 4 (max_displacement + band), so every cell has at
most one reading of its fate (one division per clone in an episode shorter
than a division cycle, as the cell's is).

Divisions follow from the diameters, so they are exact: ``bad_share`` counts
the cells whose fate is impossible (found in no role, or in more than one; a
diameter that is neither grown nor grown and halved; a type or label
changed; a dividing cell without exactly one daughter, or one found twice; a
daughter not at d/2 from her surviving mother within the walk's band), plus
the difference between the step's birth count and the divisions due. The
walk and the deaths are random, so they are judged by their statistics:

  force_z    per axis, over a sample of cells that neither divided nor died
             and lie at least 8 walk steps from the walls, the mean squared
             residual  after − before − Δ_ref  against the walk's variance
             (sigma dt)^2, over its standard error (sigma dt)^2 sqrt(2/N):
             a force dropped, halved or from the wrong neighbours, or a walk
             skipped, moves it
  death_z    deaths against Binomial(N, p), as |D − pN| / sqrt(N p (1 − p))

The force on the sample is summed over every cell by brute force in blocks
(blocks.py), in float32 under the highest matmul precision.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import blocks

WALK = 8.0          # walk steps (per axis) beyond which none is drawn
DIAMETER_TOL = 1e-5   # um: diameters are exact up to float32 rounding
NONE = 1e9          # a z-score where nothing is there to be judged


class Contact(NamedTuple):
    k_rep: float
    dtype: str


def _behavior(params: dict, name: str) -> dict:
    return next(b for b in params["behaviors"] if b["class"] == name)


def _init(q, k):
    z = jnp.zeros(q["x"].shape, jnp.float32)
    return (z, z, z)


def _step(acc, q, c, k):
    dt = jnp.dtype(k.dtype)
    d = [c[a].astype(dt)[None, :] - q[a].astype(dt)[:, None]
         for a in ("x", "y", "z")]
    dist = jnp.sqrt(jnp.maximum(d[0] * d[0] + d[1] * d[1] + d[2] * d[2],
                                jnp.asarray(1e-18, dt)))
    r_q = q["d"].astype(dt)[:, None] * 0.5
    r_c = c["d"].astype(dt)[None, :] * 0.5
    delta = r_q + r_c - dist
    r_eff = jnp.maximum(r_q * r_c / jnp.maximum(r_q + r_c, 1e-12), 1e-12)
    mag = k.k_rep * jnp.sqrt(r_eff) * jnp.maximum(delta, 0.0) ** 1.5
    pair = c["valid"][None, :] & (q["i"][:, None] != c["i"][None, :])
    push = jnp.where(pair, -mag / dist, 0.0)         # along q → c, repulsive
    return tuple(a + jnp.sum((push * di).astype(jnp.float32), axis=1)
                 for a, di in zip(acc, d))


def forces(before: dict, sample: np.ndarray, k_rep: float,
           dtype: str = "float32") -> np.ndarray:
    """(S, 3) contact force on the sampled cells from every live cell."""
    pos, dia = before["position"], before["diameter"]
    idx = np.arange(len(dia), dtype=np.int32)
    q = {"x": pos[sample, 0], "y": pos[sample, 1], "z": pos[sample, 2],
         "d": dia[sample], "i": idx[sample]}
    c = {"x": pos[:, 0], "y": pos[:, 1], "z": pos[:, 2], "d": dia, "i": idx}
    with jax.default_matmul_precision("highest"):
        fx, fy, fz = blocks.sweep(q, c, _init, _step, Contact(k_rep, dtype),
                                  q_block=128, fill={"i": -1})
    return np.stack([fx, fy, fz], axis=-1)


def displacement(force: np.ndarray, params: dict,
                 dtype=np.float32) -> np.ndarray:
    """Overdamped step F dt / ζ, cut to max_displacement in length."""
    eng = params["engine"]
    dx = force.astype(dtype) * dtype(eng["dt"] / eng["force"]["zeta"])
    norm = np.sqrt(np.maximum(np.sum(dx * dx, axis=-1, keepdims=True),
                              dtype(1e-30)))
    return dx * np.minimum(dtype(1.0), dtype(eng["force"]["max_displacement"])
                           / norm)


def growth(diameter: np.ndarray, params: dict, dtype=np.float32):
    """(divides, diameter after the step) of each cell."""
    gd = _behavior(params, "GrowDivide")
    grown = diameter.astype(dtype) + dtype(gd["rate"] * params["engine"]["dt"])
    divides = grown >= dtype(gd["threshold_diameter"])
    return divides, np.where(divides, grown * dtype(0.5 ** (1.0 / 3.0)),
                             grown)


def _within_clone(before: dict, after: dict):
    """Every (after cell, before cell) pair that shares a clone label."""
    cb, ca = before["extra.clone"], after["extra.clone"]
    ob = np.argsort(cb, kind="stable")
    lo = np.searchsorted(cb[ob], ca, side="left")
    hi = np.searchsorted(cb[ob], ca, side="right")
    n = hi - lo
    a = np.repeat(np.arange(len(ca)), n)
    start = np.repeat(lo - np.cumsum(n) + n, n)
    b = ob[start + np.arange(len(a))]
    return a, b


def compare(before: dict, after: dict, params: dict,
            sample: np.ndarray) -> dict:
    """``bad_share``, ``force_z`` and ``death_z`` of one step (module
    docstring)."""
    eng = params["engine"]
    walk = (_behavior(params, "RandomWalk")["sigma"] * eng["dt"])
    p_death = _behavior(params, "RandomDeath")["rate"]
    band = WALK * walk * np.sqrt(3.0)
    reach = eng["force"]["max_displacement"] * (1.0 + 1e-5) + band
    n_in = len(before["diameter"])
    divides, grown = growth(before["diameter"], params)
    half = 0.5 * grown.astype(np.float64)

    a, b = _within_clone(before, after)
    xa = after["position"][a].astype(np.float64)
    xb = before["position"][b].astype(np.float64)
    dist = np.sqrt(np.sum((xa - xb) ** 2, axis=-1))
    born_a, born_b = after["born_iter"][a], before["born_iter"][b]
    newest = int(after["born_iter"].max(initial=0))
    stays = (born_a == born_b) & (dist <= reach)
    daughter = (divides[b] & (born_a == newest)
                & (born_a >= int(before["born_iter"].max(initial=0)))
                & (np.abs(dist - half[b]) <= reach))
    roles = (np.bincount(a, stays, len(after["born_iter"]))
             + np.bincount(a, daughter, len(after["born_iter"])))
    kept = np.bincount(b[stays], minlength=n_in)
    born = np.bincount(b[daughter], minlength=n_in)

    # each cell found in one role: its attributes follow from its source's
    one = roles[a] == 1
    src_ok = ((np.abs(after["diameter"][a].astype(np.float64)
                      - grown[b].astype(np.float64)) <= DIAMETER_TOL)
              & (after["agent_type"][a] == before["agent_type"][b]))
    # a daughter lies at d/2 from her mother after the mother's walk
    mother_at = np.full((n_in, 3), np.nan)
    sb = stays & one
    mother_at[b[sb]] = after["position"][a[sb]]
    dm = np.sqrt(np.sum((xa - mother_at[b]) ** 2, axis=-1))
    src_ok &= ~daughter | np.isnan(dm) | (np.abs(dm - half[b]) <= band)
    good = np.zeros(len(after["born_iter"]), bool)
    good[a[(stays | daughter) & one & src_ok]] = True

    bad = int((~good).sum()) + int((kept > 1).sum()) \
        + int((divides & (born != 1)).sum()) \
        + abs(int(after["births"]) - int(divides.sum()))

    # force: the sampled cells that moved but neither divided nor died
    k_rep = eng["force"]["k_rep"]
    if eng.get("adhesion") is not None:
        raise ValueError("contact_growth: adhesion is not modelled")
    next_of = np.full(n_in, -1)
    next_of[b[sb]] = a[sb]
    s = np.asarray(sample)
    s = s[(kept[s] == 1) & ~divides[s] & (next_of[s] >= 0)]
    return {"bad_share": bad / max(n_in, 1),
            "force_z": force_z(before, after, params, s, next_of, k_rep),
            "death_z": death_z(int((kept == 0).sum()), n_in, p_death)}


def force_z(before, after, params, s, next_of, k_rep) -> float:
    """The worst axis's |mean (residual²) − w²| over its standard error, w
    the walk's step, over the sampled cells no wall can have clipped."""
    eng = params["engine"]
    w = _behavior(params, "RandomWalk")["sigma"] * eng["dt"]
    lo, hi = (np.asarray(x, np.float64) for x in params["domain"])
    x0 = before["position"][s].astype(np.float64)
    margin = eng["force"]["max_displacement"] + WALK * w
    inner = np.all((x0 > lo + margin) & (x0 < hi - margin), axis=-1)
    s, x0 = s[inner], x0[inner]
    if len(s) == 0:
        return NONE
    moved = displacement(forces(before, s, k_rep), params).astype(np.float64)
    x1 = after["position"][next_of[s]].astype(np.float64)
    r2 = (x1 - x0 - moved) ** 2
    err = w * w * np.sqrt(2.0 / len(s))
    return float(np.max(np.abs(r2.mean(axis=0) - w * w)) / err)


def death_z(deaths: int, n: int, p: float) -> float:
    """|deaths − pN| over the binomial standard deviation."""
    if n == 0:
        return NONE
    return abs(deaths - p * n) / float(np.sqrt(n * p * (1.0 - p)))


def control(before: dict, params: dict, sample: np.ndarray,
            seed: int) -> dict:
    """Outputs of the reference put in the program's place, one precision
    below the configuration's float32: the force on the sampled cells (what
    ``force_z`` reads; the rest move by none), the displacement and the
    growth in bfloat16, on positions kept in float32 as the pool keeps
    them; the walk, division directions and deaths from ``seed``."""
    eng = params["engine"]
    bf = jnp.bfloat16
    rng = np.random.default_rng(seed)
    n = len(before["diameter"])
    pos = before["position"].astype(np.float32)
    dx = np.zeros_like(pos)
    f = forces(before, sample, eng["force"]["k_rep"], "bfloat16")
    dx[sample] = np.asarray(jnp.asarray(displacement(
        np.asarray(jnp.asarray(f, bf)), params, dtype=bf), jnp.float32))
    lo, hi = (np.asarray(x, np.float32) for x in params["domain"])
    moved = np.clip(pos + dx, lo, hi)
    divides, grown = growth(np.asarray(jnp.asarray(before["diameter"], bf)),
                            params, dtype=bf)
    grown = np.asarray(jnp.asarray(grown, jnp.float32))
    u = rng.standard_normal((n, 3))
    u /= np.linalg.norm(u, axis=-1, keepdims=True)
    born_at = (moved + u * (0.5 * grown)[:, None]).astype(np.float32)
    w = _behavior(params, "RandomWalk")["sigma"] * eng["dt"]
    walked = np.clip(moved + w * rng.standard_normal((n, 3)), lo, hi)
    alive = rng.random(n) >= _behavior(params, "RandomDeath")["rate"]
    newest = int(before["born_iter"].max(initial=0)) + 1

    def cat(x, y):
        return np.concatenate([x[alive], y[divides]])
    return {"position": cat(walked.astype(np.float32), born_at),
            "diameter": cat(grown, grown),
            "agent_type": cat(before["agent_type"], before["agent_type"]),
            "born_iter": cat(before["born_iter"],
                             np.full(n, newest, before["born_iter"].dtype)),
            "extra.clone": cat(before["extra.clone"], before["extra.clone"]),
            "births": int(divides.sum())}


def numbers(before: dict, after: dict, params: dict, sample, chk: dict
            ) -> dict:
    """The cell's compared numbers for one step: every cell's fate, the
    force on ``sample``."""
    return compare(before, after, params, sample)


def control_numbers(before: dict, params: dict, sample, chk: dict,
                    seed: int) -> dict:
    """The same numbers read from the control (``control``)."""
    return compare(before, control(before, params, sample, seed), params,
                   sample)
