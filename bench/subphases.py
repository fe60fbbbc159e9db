"""Device time of the scopes nested inside the engine's phases.

``repro.core.engine.SUBPHASES`` names scopes that sit inside its
``PHASES``: K1's column-map build (``colmap_build``) and kernel (``k1``),
both inside the sweep's, and the commit's ``deaths``, ``compaction`` and
``births``. An op belongs to the innermost of all of these names in its
``op_name`` path, as in ``bench/phases.py``, whose phases it leaves as they
are: a program without the nested scopes has none of its ops here.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict

from bench import phases, trace

# the engine's nested scopes (repro.core.engine.SUBPHASES), copied: the
# reduction also reads programs that have none
SUBPHASES = ("colmap_build", "k1", "deaths", "compaction", "births")
NAMES = phases.PHASES + SUBPHASES


def phase_of(op_name: str) -> str:
    """The innermost of the phases and nested scopes in an ``op_name``
    path, else ``phases.UNSCOPED``."""
    for part in reversed(op_name.split("/")):
        if part in NAMES:
            return part
    return phases.UNSCOPED


def by_scope(red: trace.Reduction) -> Dict[str, float]:
    """Device seconds of each phase or nested scope in the reduction's
    window."""
    out: Dict[str, float] = defaultdict(float)
    for name, s in red.by_name.items():
        out[phase_of(red.labels.get(name, "").partition("] ")[2])] += s
    return dict(out)


def busy_s(red: trace.Reduction, *scopes: str) -> float:
    """Device seconds in any of ``scopes``."""
    found = by_scope(red)
    return sum(found.get(s, 0.0) for s in scopes)


def kernel_s(red: trace.Reduction, scope: str, kernel: str) -> float:
    """Device seconds of the ops in ``scope`` whose ``op_name`` path names
    ``kernel`` (a Pallas kernel's ``name``): the kernel's own launches,
    without the ops that pack and unpack its operands."""
    out = 0.0
    for name, s in red.by_name.items():
        path = red.labels.get(name, "").partition("] ")[2]
        if phase_of(path) == scope and kernel in path.split("/"):
            out += s
    return out


def busy_share(red: trace.Reduction, *scopes: str):
    """The scopes' share of device busy time (%), None where they have
    none."""
    s = busy_s(red, *scopes)
    if s <= 0:
        return None
    return 100.0 * s / red.busy_s
