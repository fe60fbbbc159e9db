"""Reduction of a profiler trace to device time, per-op time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData``:

  * device ops: the events of each device plane's ``XLA Ops`` line
    (``/device:TPU:n``), named by their HLO instruction; a ``/device:``
    plane without that line (such as the empty ``/device:CUSTOM:...``
    plane that loading the TPU library adds to a CPU trace) is no device.
    On a backend without device planes (the CPU), the host events that
    carry an ``hlo_op`` stat, which is how the CPU runtime records each XLA
    op it runs;
  * host spans: the harness's ``jax.profiler.TraceAnnotation`` events
    (``SPANS``) and the program's own (names starting ``sim.``, such as
    ``Simulation.run``'s ``sim.step`` and ``sim.overflow_check``), which
    name what the host was doing in each idle gap.

``hlo_kinds`` classifies each op by the compiled step's own HLO text, so
that a fusion's kind comes from what it contains: ``gather``, ``scatter``,
``sort``. ``reduce`` combines the two into a ``Reduction``.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, FrozenSet, List, Tuple

_EVENT = re.compile(r"^%?([\w.\-]+) = ")
SPANS = ("window", "run_call", "stats_readout", "episode_reset")
PROGRAM_SPANS = "sim."          # prefix of the program's own host spans
KIND_OPCODES = {"gather": "gather", "scatter": "scatter", "sort": "sort"}
CONTROL = ("while", "conditional", "call")   # their bodies' ops are traced


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns, on the trace's common clock
    end: float
    plane: str = ""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: a TPU names the event
    by the instruction's text (``%fusion.104 = s32[...] fusion(...)``)."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def load(path: str) -> Tuple[List[Event], List[Event]]:
    """(device ops, host spans of the harness and the program) of one trace
    file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    devices = [p for p in planes if p.name.startswith("/device:")
               and any(line.name == "XLA Ops" for line in p.lines)]
    ops, spans = [], []
    for plane in devices:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops.extend(Event(op_name(e.name), e.start_ns, e.end_ns,
                                 plane.name) for e in line.events)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS or e.name.startswith(PROGRAM_SPANS):
                    spans.append(Event(e.name, e.start_ns, e.end_ns))
                elif not devices:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops.append(Event(stats["hlo_op"], e.start_ns,
                                         e.end_ns, plane.name))
    return ops, spans


# ---------------------------------------------------------------------------
# HLO classification
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="(?:jit\([\w.\-]+\)/)?([^"]*)"')


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (after its type)."""
    i = 0
    if rhs.startswith("("):                       # tuple type: skip parens
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rhs[i:])
    return m.group(1) if m else ""


def hlo_labels(hlo_text: str) -> Dict[str, str]:
    """{instruction name: the source op it came from} (its ``op_name``
    metadata, without the program's ``jit(...)/`` prefix)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            src = _OP_NAME.search(m.group(2))
            if src:
                out[m.group(1)] = src.group(1)
    return out


def hlo_kinds(hlo_text: str) -> Dict[str, FrozenSet[str]]:
    """{instruction name: kinds} for every instruction of the module.

    A fusion takes the kinds of the instructions of the computation it calls
    (transitively). Control ops (while, conditional, call) are ``control``:
    the ops of their bodies appear in the trace by their own names, so
    ``reduce`` leaves the control op's own event out."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and current is not None:
            name, rhs = m.groups()
            comps[current].append((name, _opcode(rhs), rhs))
            continue
        m = _COMP.match(line)
        if m:
            current = m.group(1)
            comps[current] = []
    memo: Dict[str, FrozenSet[str]] = {}

    def comp_kinds(comp: str) -> FrozenSet[str]:
        if comp not in memo:
            memo[comp] = frozenset()
            memo[comp] = frozenset().union(
                *(own(n, op, rhs) for n, op, rhs in comps.get(comp, ())))
        return memo[comp]

    def own(name: str, op: str, rhs: str) -> FrozenSet[str]:
        if op in KIND_OPCODES:
            return frozenset({KIND_OPCODES[op]})
        if op in CONTROL:
            return frozenset({"control"})
        if op == "fusion":
            m = _CALLS.search(rhs)
            return comp_kinds(m.group(1)) if m else frozenset()
        return frozenset()

    return {n: own(n, op, rhs)
            for instrs in comps.values() for n, op, rhs in instrs}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Reduction:
    """Seconds of device time in the traced window, averaged over devices."""
    window_s: float
    busy_s: float
    by_name: Dict[str, float]
    by_kind: Dict[str, float]
    gaps: List[Tuple[str, float]]
    labels: Dict[str, str] = dataclasses.field(default_factory=dict)

    def kind_s(self, *kinds: str) -> float:
        """Time of ops holding any of ``kinds`` (an op counts once)."""
        return sum(v for k, v in self.by_kind.items()
                   if set(k.split("+")) & set(kinds))

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, each named with its kind and
        the source op it came from, and the longest idle gaps by host
        span."""
        ops = sorted(self.by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[" ".join(filter(None, (n, self.labels.get(n)))),
                                s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(path: str, hlo_text: str) -> Reduction:
    """The trace at ``path`` reduced against the traced program's HLO."""
    ops, spans = load(path)
    kinds = hlo_kinds(hlo_text)
    red = reduce_events(ops, spans, kinds)
    src = hlo_labels(hlo_text)
    red.labels = {n: " ".join(filter(None, (
        f"[{'+'.join(sorted(k)) or 'other'}]", src.get(n))))
        for n, k in kinds.items()}
    return red


def reduce_events(ops: List[Event], spans: List[Event],
                  kinds: Dict[str, FrozenSet[str]]) -> Reduction:
    """Busy time is the union of op intervals inside the harness's
    ``window`` span, control ops left out; an op's kind key joins its
    sorted kinds with ``+``
    (``other`` for none); each idle gap is named by the innermost host
    span around its middle, the harness's or the program's."""
    win = [s for s in spans if s.name == "window"]
    lo = min(s.start for s in win) if win else min(o.start for o in ops)
    hi = max(s.end for s in win) if win else max(o.end for o in ops)
    planes = sorted({o.plane for o in ops})
    by_name: Dict[str, float] = defaultdict(float)
    by_kind: Dict[str, float] = defaultdict(float)
    busy, gaps = 0.0, []
    inner = [s for s in spans if s.name != "window"]
    for plane in planes:
        clipped = [(max(o.start, lo), min(o.end, hi), o.name) for o in ops
                   if o.plane == plane and o.end > lo and o.start < hi
                   and "control" not in kinds.get(o.name, ())]
        for s, e, name in clipped:
            by_name[name] += (e - s) * 1e-9
            kind = "+".join(sorted(kinds.get(name, ()))) or "other"
            by_kind[kind] += (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = 0.5 * (s + e)
                around = [sp for sp in inner if sp.start <= mid <= sp.end]
                label = (min(around, key=lambda sp: sp.end - sp.start).name
                         if around else "outside_spans")
                gaps.append((label, (e - s) * 1e-9))
    n = max(len(planes), 1)
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy / n,
                     by_name={k: v / n for k, v in by_name.items()},
                     by_kind={k: v / n for k, v in by_kind.items()},
                     gaps=gaps)

