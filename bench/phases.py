"""Device time by phase of the engine's step, and idle gaps named by the
program's run-loop spans, from the benchmark's trace reduction.

The engine wraps each phase of its step in a ``jax.named_scope``
(``repro.core.engine.PHASES``), so each compiled op's ``op_name`` path
carries the phases it was issued in. An op belongs to the innermost phase
in its path, and to ``UNSCOPED`` where there is none: a program without the
scopes has every op unscoped. ``Simulation.run`` marks each step's dispatch
``sim.step`` and its flag read ``sim.overflow_check`` on the profiler's
clock; ``gaps`` names each idle gap by the innermost harness or program
span around it.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace

# the engine's phase scopes (repro.core.engine.PHASES), copied: the
# reduction also reads programs that have none
PHASES = ("grid_build", "pairlist_build", "statics", "diffusion",
          "neighbor_sweep", "behaviors", "health", "commit")
UNSCOPED = "unscoped"
PROGRAM_SPANS = "sim."          # prefix of the program's own host spans


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


def program_spans(path: str) -> List[trace.Event]:
    """The program's host spans (names starting ``sim.``) of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [trace.Event(e.name, e.start_ns, e.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_SPANS)]


def gaps(path: str, hlo_text: str) -> List[Tuple[str, float]]:
    """The traced window's idle gaps, (innermost harness or program span,
    seconds)."""
    ops, spans = trace.load(path)
    red = trace.reduce_events(ops, spans + program_spans(path),
                              trace.hlo_kinds(hlo_text))
    return red.gaps
