"""What the program has measured of itself in this process.

For a monitor that holds none of the program's objects, such as a
benchmark's metric reader or a dashboard polled between runs:

  * ``compile_seconds()``: wall seconds JAX spent tracing, lowering, and
    compiling or loading compiled programs since this module was imported,
    from its ``jax.monitoring`` duration events under ``/jax/core/compile/``.
    A nested ``jit`` is traced inside its caller's trace and reports its own
    event, so the events are merged as intervals, not summed. The persistent
    cache's ``/jax/compilation_cache/compile_time_saved_sec`` is time not
    spent, and is not one of them.
  * ``last_step()``: the last step whose flags ``Simulation.run`` checked:
    its work counters (``StepStats.WORK_FIELDS``) as host ints summed over
    shards or lanes, and ``compile_seconds()`` as it stood then, which
    leaves out what the process compiled after its simulation stepped;
    None before the first such step.
  * ``step_totals()``: the same work counters summed over every step whose
    flags ``Simulation.run`` checked in this process, with the number of
    those steps; None before the first.

Importing the module registers the listener; ``repro.core.engine`` imports
it, so every compile of a simulation is seen.
"""

from __future__ import annotations

import time
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

import jax.monitoring

COMPILE_EVENTS = "/jax/core/compile/"

_compiles: List[Tuple[float, float]] = []   # disjoint (start, end), by end
_compile_s = 0.0                            # their total


class StepRecord(NamedTuple):
    counts: Dict[str, int]
    compile_s: float


class StepTotals(NamedTuple):
    steps: int
    counts: Dict[str, int]


_last_step: Optional[StepRecord] = None
_totals: Optional[StepTotals] = None


def _on_duration(event: str, duration: float, **_) -> None:
    if not event.startswith(COMPILE_EVENTS):
        return
    end = time.perf_counter()
    start = end - duration
    global _compile_s
    # an event ends after every event nested in it: it replaces them
    while _compiles and _compiles[-1][0] >= start:
        s, e = _compiles.pop()
        _compile_s -= e - s
    _compiles.append((start, end))
    _compile_s += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> float:
    return _compile_s


def record_step(counts: Mapping[str, int]) -> None:
    global _last_step, _totals
    _last_step = StepRecord(dict(counts), compile_seconds())
    steps, total = _totals or (0, {})
    _totals = StepTotals(steps + 1, {k: total.get(k, 0) + v
                                     for k, v in counts.items()})


def last_step() -> Optional[StepRecord]:
    return _last_step


def step_totals() -> Optional[StepTotals]:
    return _totals
