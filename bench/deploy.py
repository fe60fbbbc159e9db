"""Deployments and their initial populations, built from the benchmark's data.

A configuration file (``bench/configs/<name>.json``) pins every
``EngineConfig`` value, the behaviors with their parameters, the rules that
size the domain and the pool from the population, and the population
generator. A workload file (``bench/workloads/<cell>.json``) may override
population keys. The values are copied from ``repro.launch.simulate``'s
scenarios and not imported from it, so a change there does not move the
yardstick.

Population keys:
  agents       number of seed agents
  region       [lo, hi] fractions of the domain edge; positions are uniform
               in that sub-cube
  diameter     [lo, hi] uniform diameters (lo == hi: all equal)
  tag_diameter true: diameter_i = lo * (1 + rank_i * 2**-23) with rank a
               random permutation, so every agent carries a distinct exact
               value. Only for deployments whose dynamics never read the
               diameter; the reference uses it as the agent's identity.
  seed_type    {"value", "share", "min"}: the first max(int(agents * share),
               min) agents (a random spatial subset) get agent_type value
  extra        {channel: constant} initial values of behavior channels
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (EngineConfig, ForceParams, HealthConfig,
                        PairListConfig, RebuildPolicy, Simulation)
from repro.core import behaviors as behavior_classes
from repro.core.agents import DtypePolicy
from repro.core.diffusion import DiffusionSpec

NESTED = {"force": ForceParams, "rebuild": RebuildPolicy,
          "dtypes": DtypePolicy, "health": HealthConfig,
          "pairlist": PairListConfig, "diffusion": DiffusionSpec}

TAG_BITS = 23        # float32 mantissa: ranks below 2**23 stay exact


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


@dataclasses.dataclass(frozen=True)
class Deployment:
    """One configuration at one population: engine, behaviors, generator."""
    name: str
    config: EngineConfig
    behavior_specs: tuple
    population: dict
    side: float

    def behaviors(self):
        out = []
        for spec in self.behavior_specs:
            params = {k: v for k, v in spec.items() if k != "class"}
            out.append(getattr(behavior_classes, spec["class"])(**params))
        return out

    def simulation(self) -> Simulation:
        return Simulation(self.config, self.behaviors())

    @property
    def n_agents(self) -> int:
        return int(self.population["agents"])


def deployment(config: dict, population_overrides: dict | None = None,
               agents: int | None = None) -> Deployment:
    """The deployment a configuration file describes; ``agents`` rescales
    the population (tests run the same deployment at a tiny size)."""
    pop = dict(config["population"])
    pop.update(population_overrides or {})
    if agents is not None:
        pop["agents"] = agents
    n = int(pop["agents"])
    dom, cap = config["domain"], config["capacity"]
    side = max(dom["min_side"], n ** (1 / 3) * dom["side_per_cbrt_agent"])
    kw = {}
    for key, value in config["engine"].items():
        if key in NESTED and value is not None:
            value = NESTED[key](**{k: _tuples(v) for k, v in value.items()})
        kw[key] = _tuples(value)
    engine = EngineConfig(capacity=max(cap["per_agent"] * n, cap["min"]),
                          domain_lo=(0.0,) * 3, domain_hi=(side,) * 3, **kw)
    if pop.get("tag_diameter") and n > 1 << TAG_BITS:
        raise ValueError(f"tag_diameter holds at most 2**{TAG_BITS} agents")
    return Deployment(config["name"], engine, tuple(config["behaviors"]),
                      pop, side)


def seed_words(seed: int) -> np.ndarray:
    """Four uint32 words from any whole number (seeds exceed 32 bits)."""
    return np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)


def initial_state(dep: Deployment, sim: Simulation, seed: int):
    """The seed's initial population, made on the device in one jitted call."""
    pop, n, side = dep.population, dep.n_agents, dep.side

    def make(key, engine_seed):
        k_pos, k_dia, k_rank = jax.random.split(key, 3)
        lo, hi = pop["region"]
        pos = jax.random.uniform(k_pos, (n, 3), jnp.float32,
                                 lo * side, hi * side)
        d_lo, d_hi = pop["diameter"]
        if pop.get("tag_diameter"):
            rank = jax.random.permutation(k_rank, n).astype(jnp.float32)
            dia = d_lo * (1.0 + rank * 2.0 ** -TAG_BITS)
        elif d_lo == d_hi:
            dia = jnp.full((n,), d_lo, jnp.float32)
        else:
            dia = jax.random.uniform(k_dia, (n,), jnp.float32, d_lo, d_hi)
        types = jnp.zeros((n,), jnp.int32)
        if "seed_type" in pop:
            st = pop["seed_type"]
            count = max(int(n * st["share"]), st["min"])
            types = jnp.where(jnp.arange(n) < count, st["value"], types)
        extra = {k: jnp.full((n,), v) for k, v in pop.get("extra", {}).items()}
        return sim.init_state(pos, dia, types, extra or None,
                              seed=engine_seed)

    words = seed_words(seed)
    key = jnp.asarray(words[:2], jnp.uint32)
    engine_seed = jnp.asarray(words[2] >> 1, jnp.int32)
    return jax.jit(make)(key, engine_seed)
