"""Behavior system — paper §2: behaviors are per-agent actions; operations apply them.

Behaviors read the step context (pool, grid, diffusion, RNG) and return
*effects*: channel updates, staged births, death marks, substance secretion.
The engine merges effects and commits them in the iteration epilogue —
mirroring BioDynaMo's thread-local staging + end-of-iteration commit (§3.2).

**Ownership contract (DESIGN.md §7):** a behavior's base mask is
``ctx.owned``, never ``pool.alive``. Under the single-device engine the two
are identical; under the distributed engine ``pool.alive`` additionally
covers *ghost* rows — boundary agents copied in from neighboring slabs as
force/neighbor sources. Acting on a ghost (staging its division, marking its
death) would duplicate the effect its owning shard commits authoritatively.
Ghosts still appear as *neighbors* in ``ctx.neighbor_apply`` reductions,
which is exactly what makes cross-slab interactions exact.

All per-agent randomness is drawn through :mod:`rand` (capacity-stable
threefry streams): the value an agent sees depends on (key, slot, lane) but
never on the pool's capacity, so the capacity ladder (DESIGN.md §4.3) can
grow the pool mid-run without perturbing the trajectory — ``jax.random``'s
array draws do not have this property.

The catalogue below covers the paper's five benchmark simulations (Table 1):
  GrowDivide          cell proliferation / oncology (create agents)
  Lineage             oncology (clone labels that daughters inherit)
  RandomWalk          epidemiology / oncology (agents move randomly)
  Infection+Recovery  epidemiology (SIR over spatial neighbors)
  Chemotaxis          cell clustering (move along substance gradient)
  Secretion           cell clustering / neuroscience (substance sources)
  RandomDeath         oncology (delete agents)
  NeuriteGrowth       neuroscience (growth cones + static trail + bifurcation)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import rand
from .agents import AgentPool
from .grid import PairKernel


def resolve(value, ctx):
    """Realize a behavior knob against the step context.

    Every numeric behavior parameter (``Infection.beta``, ``RandomWalk.sigma``,
    ``GrowDivide.rate``, ...) accepts either a plain number — the static,
    compiled-in value — or a *callable* ``ctx -> value`` evaluated at trace
    time against the :class:`~.engine.StepContext`. The callable form is how
    ensemble lanes get per-lane rates without recompiling: pass
    ``Infection(beta=lambda ctx: ctx.params["beta"])`` and feed the rate
    through ``ScenarioParams(rates={"beta": ...})`` — under
    ``make_ensemble_core`` the traced scalar differs per lane while the
    program stays one compilation.
    """
    return value(ctx) if callable(value) else value


@dataclasses.dataclass
class BehaviorEffects:
    """What a behavior wants to change. All optional; engine merges in order."""
    set_channels: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    birth_channels: Optional[Dict[str, jnp.ndarray]] = None   # (Q, ...) staged agents
    birth_valid: Optional[jnp.ndarray] = None                 # (Q,) bool
    death_mask: Optional[jnp.ndarray] = None                  # (C,) bool
    secretion: Optional[jnp.ndarray] = None                   # (C,) amounts


class Behavior:
    """Base class. Subclasses override extra_specs() and __call__().

    Neighbor-using behaviors additionally override :meth:`neighbor_kernels`
    to declare their pair kernels with an explicit channel footprint
    (grid.PairKernel). The engine registers every declared kernel into ONE
    fused sweep per step (together with the collision force) and hands the
    results back through ``ctx.neighbor_results[kernel.name]`` — the 9 z-runs
    are gathered once per block for all of them, pruned to the union of
    declared footprints (DESIGN.md §3.2). ``__call__`` should consume
    ``ctx.neighbor_results`` when its kernel name is present and fall back to
    ``ctx.neighbor_apply`` otherwise (sequential path: non-uniform-grid
    environments, or ``EngineConfig.fused_sweep=False``).
    """

    name: str = "behavior"

    def extra_specs(self) -> Dict[str, tuple]:
        """Channels this behavior needs: name → (shape_suffix, dtype, fill)."""
        return {}

    def neighbor_kernels(self) -> Tuple[PairKernel, ...]:
        """Pair kernels to register into the step's fused neighbor sweep."""
        return ()

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        raise NotImplementedError


class GrowDivide(Behavior):
    """Grow diameter at ``rate``; split once above ``threshold_diameter``.

    Division: mother shrinks to volume/2, daughter (staged birth) placed at a
    random direction at center distance = mother radius (BioDynaMo CellDivision).
    The daughter copies the mother's type and behavior channels (``extra.*``),
    as BioDynaMo's division copies the mother's attributes.
    """

    name = "grow_divide"

    def __init__(self, rate: float = 1.0, threshold_diameter: float = 12.0,
                 applies_to: int | None = None):
        self.rate = rate
        self.threshold = threshold_diameter
        self.applies_to = applies_to

    def _mask(self, ctx, pool):
        m = ctx.owned
        if self.applies_to is not None:
            m &= pool.agent_type == self.applies_to
        return m

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        mask = self._mask(ctx, pool)
        rate = resolve(self.rate, ctx)
        threshold = resolve(self.threshold, ctx)
        new_dia = jnp.where(mask, pool.diameter + rate * ctx.dt, pool.diameter)
        divide = mask & (new_dia >= threshold)
        # halve the volume: d' = d / 2^(1/3)
        halved = new_dia * (0.5 ** (1.0 / 3.0))
        mother_dia = jnp.where(divide, halved, new_dia)
        # daughter placement (capacity-stable draw: ladder parity)
        direction = rand.normal_rows(rng, pool.capacity, 3)
        direction /= jnp.sqrt(
            jnp.sum(direction * direction, -1, keepdims=True) + 1e-12)
        d_pos = pool.position + direction * (mother_dia * 0.5)[:, None]
        inherited = {"extra." + k: v for k, v in pool.extra.items()}
        return BehaviorEffects(
            set_channels={"diameter": mother_dia},
            birth_channels={**inherited, "position": d_pos,
                            "diameter": mother_dia,
                            "agent_type": pool.agent_type},
            birth_valid=divide,
        )


class Lineage(Behavior):
    """Clone labels for lineage tracing: every seeded agent carries its own
    label (its seed index + 1, in channel ``extra.clone``), and a daughter
    inherits her mother's (GrowDivide). The label never acts on the
    dynamics; it says which seed cell each agent descends from."""

    name = "lineage"

    def extra_specs(self):
        return {"clone": ((), jnp.int32, lambda slot: slot + 1)}

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        return BehaviorEffects()


class RandomWalk(Behavior):
    """Brownian step of scale sigma (epidemiology/oncology random movement)."""

    name = "random_walk"

    def __init__(self, sigma: float = 1.0, applies_to: int | None = None):
        self.sigma = sigma
        self.applies_to = applies_to

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        mask = ctx.owned
        if self.applies_to is not None:
            mask &= pool.agent_type == self.applies_to
        step = resolve(self.sigma, ctx) * rand.normal_rows(rng, pool.capacity, 3)
        new_pos = jnp.where(mask[:, None], pool.position + step * ctx.dt,
                            pool.position)
        new_pos = jnp.clip(new_pos, ctx.domain_lo, ctx.domain_hi)
        return BehaviorEffects(set_channels={"position": new_pos})


# SIR agent_type encoding used by the epidemiology simulation.
SUSCEPTIBLE, INFECTED, RECOVERED = 0, 1, 2


class Infection(Behavior):
    """SIR infection over spatial neighbors (paper epidemiology use case).

    Susceptible agents with ≥1 infected neighbor within ``radius`` become
    infected with probability ``beta``; infected agents recover after
    ``recovery_time`` iterations (timer channel).
    """

    name = "infection"

    def __init__(self, radius: float = 2.0, beta: float = 0.3,
                 recovery_time: int = 50):
        self.radius = radius
        self.beta = beta
        self.recovery_time = recovery_time

    def extra_specs(self):
        return {"infect_timer": ((), jnp.int32, 0)}

    def _pair_fn(self):
        r = self.radius

        def pair_fn(q, nbr, valid, q_slot):
            d = nbr["position"] - q["position"][:, None, :]
            dist2 = jnp.sum(d * d, axis=-1)
            # NOTE the INCLUSIVE dist² ≤ r² test: the pair-list build filter
            # (grid.build_pairlist) is inclusive at (r+skin)² for exactly
            # this reason — an equality-distance infected neighbor must
            # survive the pruning. Out-of-range candidates contribute int 0
            # to the OR-count, so pruning/stale extras are exact no-ops.
            exposed = valid & nbr["alive"] & (nbr["agent_type"] == INFECTED) \
                & (dist2 <= r * r)
            # OR encoded as an additive count across the 9 streamed runs;
            # the consumer thresholds it (resident_apply output contract)
            return {"exposed": jnp.any(exposed, axis=-1).astype(jnp.int32)}

        return pair_fn

    def neighbor_kernels(self):
        return (PairKernel(name=self.name, pair_fn=self._pair_fn(),
                           out_specs={"exposed": ((), jnp.int32)},
                           reads=("position", "alive", "agent_type")),)

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        res = ctx.neighbor_results.get(self.name)
        if res is None:   # sequential path: its own sweep over the same
            res = ctx.neighbor_apply(self._pair_fn(),   # pre-force snapshot
                                     {"exposed": ((), jnp.int32)})
        exposed = res["exposed"] > 0
        u = rand.uniform_rows(rng, pool.capacity)
        newly = ctx.owned & (pool.agent_type == SUSCEPTIBLE) & exposed \
            & (u < resolve(self.beta, ctx))
        timer = pool.extra["infect_timer"]
        recovery = jnp.asarray(resolve(self.recovery_time, ctx), timer.dtype)
        timer = jnp.where(newly, recovery, timer)
        is_inf = pool.agent_type == INFECTED
        timer = jnp.where(is_inf, timer - 1, timer)
        recovered = is_inf & (timer <= 0)
        new_type = jnp.where(newly, INFECTED, pool.agent_type)
        new_type = jnp.where(recovered, RECOVERED, new_type)
        return BehaviorEffects(
            set_channels={"agent_type": new_type, "extra.infect_timer": timer})


class Chemotaxis(Behavior):
    """Move up the gradient of the diffusion substance (cell clustering)."""

    name = "chemotaxis"

    def __init__(self, speed: float = 0.5):
        self.speed = speed

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        g = ctx.substance_gradient(pool.position)           # (C, 3)
        norm = jnp.sqrt(jnp.sum(g * g, -1, keepdims=True) + 1e-12)
        step = resolve(self.speed, ctx) * ctx.dt * g / norm
        new_pos = jnp.where(ctx.owned[:, None], pool.position + step,
                            pool.position)
        new_pos = jnp.clip(new_pos, ctx.domain_lo, ctx.domain_hi)
        return BehaviorEffects(set_channels={"position": new_pos})


class Secretion(Behavior):
    """Secrete ``rate`` into the substance grid at the agent's voxel."""

    name = "secretion"

    def __init__(self, rate: float = 1.0, applies_to: int | None = None):
        self.rate = rate
        self.applies_to = applies_to

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        mask = ctx.owned
        if self.applies_to is not None:
            mask &= pool.agent_type == self.applies_to
        return BehaviorEffects(
            secretion=jnp.where(mask, resolve(self.rate, ctx) * ctx.dt, 0.0))


class RandomDeath(Behavior):
    """Remove agents with probability ``rate`` per iteration (oncology)."""

    name = "random_death"

    def __init__(self, rate: float = 0.001, applies_to: int | None = None):
        self.rate = rate
        self.applies_to = applies_to

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        mask = ctx.owned
        if self.applies_to is not None:
            mask &= pool.agent_type == self.applies_to
        u = rand.uniform_rows(rng, pool.capacity)
        return BehaviorEffects(death_mask=mask & (u < resolve(self.rate, ctx)))


# Neuroscience: growth cones extend and leave a static trail (paper §5:
# "neural development simulations might only have an active growth front,
# while the remaining part of the neuron is unchanged").
SOMA, NEURITE_SEGMENT, GROWTH_CONE = 10, 11, 12


class NeuriteGrowth(Behavior):
    """Growth cones elongate along a persistent direction with noise, deposit
    NEURITE_SEGMENT agents behind them, and occasionally bifurcate."""

    name = "neurite_growth"

    def __init__(self, speed: float = 1.0, noise: float = 0.15,
                 bifurcation_prob: float = 0.004, segment_every: float = 2.0):
        self.speed = speed
        self.noise = noise
        self.bif_prob = bifurcation_prob
        self.segment_every = segment_every

    def extra_specs(self):
        return {"direction": ((3,), jnp.float32, 0.0),
                "path_len": ((), jnp.float32, 0.0)}

    def __call__(self, ctx, pool: AgentPool, rng: jax.Array) -> BehaviorEffects:
        k1, k2, k3 = jax.random.split(rng, 3)
        cones = ctx.owned & (pool.agent_type == GROWTH_CONE)
        d = pool.extra["direction"]
        d = d + self.noise * rand.normal_rows(k1, pool.capacity, 3)
        d /= jnp.sqrt(jnp.sum(d * d, -1, keepdims=True) + 1e-12)
        step = self.speed * ctx.dt
        new_pos = jnp.where(cones[:, None], pool.position + d * step, pool.position)
        new_pos = jnp.clip(new_pos, ctx.domain_lo, ctx.domain_hi)
        path = jnp.where(cones, pool.extra["path_len"] + step, pool.extra["path_len"])

        # deposit a (soon static) segment agent at the old position
        deposit = cones & (path >= self.segment_every)
        path = jnp.where(deposit, 0.0, path)
        seg_type = jnp.full_like(pool.agent_type, NEURITE_SEGMENT)

        # bifurcation: stage a second cone with a rotated direction
        u = rand.uniform_rows(k2, pool.capacity)
        bif = cones & (u < self.bif_prob)
        rot = d + 0.8 * rand.normal_rows(k3, pool.capacity, 3)
        rot /= jnp.sqrt(jnp.sum(rot * rot, -1, keepdims=True) + 1e-12)
        cone_type = jnp.full_like(pool.agent_type, GROWTH_CONE)

        birth = {
            "position": jnp.concatenate([pool.position, new_pos], 0),
            "diameter": jnp.concatenate([pool.diameter, pool.diameter], 0),
            "agent_type": jnp.concatenate([seg_type, cone_type], 0),
            "extra.direction": jnp.concatenate([jnp.zeros_like(d), rot], 0),
            "extra.path_len": jnp.zeros((2 * pool.capacity,), path.dtype),
        }
        valid = jnp.concatenate([deposit, bif], 0)
        return BehaviorEffects(
            set_channels={"position": new_pos, "extra.direction": d,
                          "extra.path_len": path},
            birth_channels=birth, birth_valid=valid)
