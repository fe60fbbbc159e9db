"""Device time by phase of the engine's step, from the benchmark's trace
reduction.

The engine wraps each phase of its step in a ``jax.named_scope``
(``repro.core.engine.PHASES``), so each compiled op's ``op_name`` path
carries the phases it was issued in. An op belongs to the innermost phase
in its path, and to ``UNSCOPED`` where there is none: a program without the
scopes has every op unscoped.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Optional

from bench import trace

# the engine's phase scopes (repro.core.engine.PHASES), copied: the
# reduction also reads programs that have none
PHASES = ("grid_build", "pairlist_build", "statics", "diffusion",
          "neighbor_sweep", "behaviors", "health", "commit")
UNSCOPED = "unscoped"


def phase_of(op_name: str) -> str:
    """The innermost of ``PHASES`` in an ``op_name`` path, else
    ``UNSCOPED``."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return UNSCOPED


def by_phase(red: trace.Reduction) -> Dict[str, float]:
    """Device seconds of each phase in the reduction's window. An op's
    source ``op_name`` is its label without the ``[kinds]`` tag."""
    out: Dict[str, float] = defaultdict(float)
    for name, s in red.by_name.items():
        out[phase_of(red.labels.get(name, "").partition("] ")[2])] += s
    return dict(out)


def busy_share(red: trace.Reduction, phase: str) -> Optional[float]:
    """The phase's share of device busy time (%), None where it has none."""
    s = by_phase(red).get(phase, 0.0)
    if s <= 0:
        return None
    return 100.0 * s / red.busy_s
