"""Pure-jnp oracles for every Pallas kernel (ground truth for allclose tests)."""

from __future__ import annotations

import jax.numpy as jnp


def collision_force_ref(position: jnp.ndarray, diameter: jnp.ndarray,
                        agent_type: jnp.ndarray, alive: jnp.ndarray,
                        k_rep: float, adhesion: tuple | None,
                        adhesion_band: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dense O(N²) Cortex3D force (same math as core.forces.pair_force).

    Returns (force (N,3), nnz (N,) int32). Only pairs with both endpoints alive
    interact; self-pairs excluded. ``adhesion`` is a nested tuple (T,T) or None.
    """
    n = position.shape[0]
    d = position[None, :, :] - position[:, None, :]           # (N, N, 3) q->n
    dist = jnp.sqrt(jnp.maximum(jnp.sum(d * d, -1), 1e-18))
    r_q = diameter[:, None] * 0.5
    r_n = diameter[None, :] * 0.5
    delta = r_q + r_n - dist
    r_eff = jnp.maximum(r_q * r_n / jnp.maximum(r_q + r_n, 1e-12), 1e-12)
    f_rep = k_rep * jnp.sqrt(r_eff) * jnp.power(jnp.maximum(delta, 0.0), 1.5)
    if adhesion is not None:
        adh = jnp.asarray(adhesion, jnp.float32)
        mu = adh[agent_type[:, None], agent_type[None, :]]
        band = jnp.maximum(delta + adhesion_band, 0.0)
        f_adh = jnp.where(delta + adhesion_band > 0.0,
                          mu * jnp.sqrt(r_eff * band), 0.0)
    else:
        f_adh = 0.0
    f_mag = f_rep - f_adh
    valid = (alive[:, None] & alive[None, :]
             & ~jnp.eye(n, dtype=bool)
             & (delta + adhesion_band > 0.0))
    direction = d / dist[..., None]
    pair = jnp.where(valid[..., None], -f_mag[..., None] * direction, 0.0)
    force = jnp.sum(pair, axis=1)
    nnz = jnp.sum(jnp.sum(pair * pair, -1) > (1e-7) ** 2, axis=1).astype(jnp.int32)
    return force, nnz

