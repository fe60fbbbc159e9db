"""The chip benchmark's harness: one cell, one process, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, cell or per-layer metric is
data found by name under ``bench/`` (``Bench``): ``configs/<config>.json``,
``workloads/<cell>.json``, ``metrics/<metric>.py``, and the plain reference
module a configuration names under ``reference/``. ``BENCHMARK.json`` at the
checkout root lists the cells and metrics.

A run builds the cell's deployment and its seed's initial population on the
device, compiles the step (set-up), then replays a fixed episode of the
cell's steps from that initial state through ``Simulation.run(state, 1,
check_overflow=True)`` until ``--seconds`` have passed (the window), reads
the device's peak memory, frees the program's state and compares the first
and the last completed step of the window with the plain reference
(``correct``). The window holds the same device states however many steps
fit in it (``solo_window``), so the peak does not count them.
``--trace 1`` instead traces a few steps of the episode under the profiler
and reports the cell's per-layer metrics from that trace.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
GIB = float(1 << 30)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Bench:
    """The benchmark's data, found by name under ``bench_dir``."""

    def __init__(self, bench_dir: Path = BENCH_DIR, root: Path = ROOT):
        self.dir = Path(bench_dir)
        self.spec = json.loads((Path(root) / "BENCHMARK.json").read_text())

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py``, imported from this directory."""
        path = self.dir / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        """The cell's workload file, checked against its BENCHMARK.json
        entry."""
        entry = next((w for w in self.spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise ValueError(f"cell {name}: not in BENCHMARK.json")
        cell = self._json("workloads", name)
        if cell["config"] != entry["config"]:
            raise ValueError(f"cell {name}: workload file names config "
                             f"{cell['config']!r}, BENCHMARK.json "
                             f"{entry['config']!r}")
        return dict(cell, name=name, chips=entry["chips"])

    def metrics(self, kind: str, cell: str) -> list:
        """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# device, compile cache, spans
# ---------------------------------------------------------------------------

def require_devices(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devices)} {d0.platform} "
                       f"device(s) ({d0.device_kind})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} TPU chips, JAX found "
                       f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` at the checkout root (a fixed path: the
    path is part of the cache's key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of a run, however quick to compile, is found again by
    # the next run, so that set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Spans:
    """Host spans in the profiler's trace (``--trace 1``); free otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    attempted: int = 0
    failed: int = 0
    failed_at: list = dataclasses.field(default_factory=list)   # episode steps
    agent_steps: int = 0
    entering: list = dataclasses.field(default_factory=list)
    seconds: float = 0.0
    first: tuple = ()             # the first completed step: its (before,
                                  # after) states, or ``host_pair``'s copy
    last: tuple = ()              # the last completed step's states, () while
                                  # the step after it runs or once it failed


def solo_window(sim, s0, n0: int, episode: int, *, seconds: float = 0.0,
                steps: int = 0, spans: Spans = Spans(False)) -> Window:
    """Replay the episode from ``s0`` through ``Simulation.run`` one step a
    call, until ``seconds`` have passed (or for ``steps`` steps).

    ``agent_steps`` sums the live agents entering each completed step; a
    step whose counters carry an overflow or health flag counts as failed
    (it dropped interactions), and one that raised restarts the episode.

    At every dispatch the window holds three states on the device, however
    many steps fit in it: ``s0`` for the resets, the step's input and its
    output. The last completed step's pair is released before the next
    dispatch (its ``after`` is that step's input), and the first step's
    pair is copied to the host before the first dispatch whose input is not
    its ``after``, with the clock paused: once a run, and never in a window
    of two steps."""
    w = Window()
    cur, n_in, t_ep = s0, n0, 0
    paused = 0.0
    t0 = time.perf_counter()
    while True:
        if w.first and not _on_host(w.first) and cur is not w.first[1]:
            t_copy = time.perf_counter()
            w.first = host_pair(*w.first)
            paused += time.perf_counter() - t_copy
        w.last, nxt = (), None
        w.attempted += 1
        with spans("run_call"):
            try:
                nxt = sim.run(cur, 1, check_overflow=True)
            except RuntimeError:
                pass
        if nxt is None:
            w.failed += 1
            w.failed_at.append(t_ep + 1)
            cur, n_in, t_ep = s0, n0, 0
        else:
            with spans("stats_readout"):
                n_out = int(nxt.stats.n_live)
                if nxt.stats.health_bits():
                    w.failed += 1
                    w.failed_at.append(t_ep + 1)
            w.agent_steps += n_in
            w.entering.append(n_in)
            w.last = (cur, nxt)
            w.first = w.first or w.last
            cur, n_in, t_ep = nxt, n_out, t_ep + 1
            if t_ep == episode:
                with spans("episode_reset"):
                    cur, n_in, t_ep = s0, n0, 0
        w.seconds = time.perf_counter() - t0 - paused
        if (steps and w.attempted >= steps) or (not steps
                                                and w.seconds >= seconds):
            return w


def live_arrays(state) -> dict:
    """The live agents' channels of an engine state, as numpy arrays."""
    ch = state.pool.channels()
    alive = np.asarray(ch["alive"])
    return {k: np.asarray(v)[alive] for k, v in ch.items() if k != "alive"}


def host_pair(before, after) -> tuple:
    """A completed step's (before, after) live arrays, with the step's birth
    count."""
    arrays = (live_arrays(before), live_arrays(after))
    arrays[1]["births"] = int(after.stats.births)
    return arrays


def _on_host(pair: tuple) -> bool:
    return isinstance(pair[0], dict)


def checked_steps(win: Window) -> list:
    """(before, after) live arrays of the window's first and last completed
    step (``host_pair``); the first alone where the window's last dispatch
    failed, and none where no step completed."""
    pairs = [win.first] if win.first else []
    if win.last and win.last is not win.first:
        pairs.append(win.last)
    return [p if _on_host(p) else host_pair(*p) for p in pairs]


def reference_params(config: dict, dep) -> dict:
    return {"engine": config["engine"], "behaviors": config["behaviors"],
            "population": dep.population,
            "domain": [list(dep.config.domain_lo), list(dep.config.domain_hi)]}


def sample_of(n: int, size: int, seed: int) -> np.ndarray:
    """``size`` live agents drawn from the seed (all of them when fewer)."""
    from bench.deploy import seed_words
    if n <= size:
        return np.arange(n)
    rng = np.random.default_rng(seed_words(seed)[3])
    return np.sort(rng.choice(n, size, replace=False))


def check(bench: Bench, cell: dict, config: dict, dep, steps: list,
          seed: int) -> dict:
    """The cell's numbers against its limits, {name: {value, limit}}, each
    the worst over the checked steps, given as (before, after) live arrays.
    """
    ref = bench.module("reference", config["reference"])
    chk = cell["check"]
    params = reference_params(config, dep)
    worst: dict = {}
    for before, after in steps:
        sample = sample_of(len(before["diameter"]), chk.get("sample", 0), seed)
        for k, v in ref.numbers(before, after, params, sample, chk).items():
            worst[k] = max(worst.get(k, v), v)
    return {k: {"value": v, "limit": chk["limits"][k]}
            for k, v in worst.items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    params: dict                  # the configuration's values (reference)
    reduction: object             # trace.Reduction of the traced window
    device_kind: str
    seed: int


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict,
             agents: int | None = None, population: dict | None = None
             ) -> dict:
    """Set-up, window, reference check and metrics of one run; ``agents``
    rescales the population and ``population`` overrides its keys (the CPU
    tests run cells at a tiny size)."""
    import jax
    from bench import deploy
    from bench import trace as trace_mod

    cell = bench.cell(cell_name)
    if cell["driver"] != "solo":
        raise ValueError(f"cell {cell_name}: no driver {cell['driver']!r}")
    config = bench.config(cell["config"])
    dep = deploy.deployment(
        config, dict(cell.get("population", {}), **(population or {})), agents)
    sim = dep.simulation()
    s0 = jax.block_until_ready(deploy.initial_state(dep, sim, seed))
    compiled = sim._step_fn.lower(s0).compile()    # the window's one program
    s0.stats.flags()                               # and its counter readout
    s0.stats.health_bits()
    setup_s = time.perf_counter() - t_start

    spans = Spans(trace)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        with spans("window"):
            win = solo_window(sim, s0, dep.n_agents, cell["episode_steps"],
                              seconds=seconds,
                              steps=cell["trace_steps"] if trace else 0,
                              spans=spans)
        if trace:
            jax.profiler.stop_trace()
        peak = peak_bytes()
        out = {"attempted": win.attempted, "failed": win.failed}
        values = {"agent_steps_per_s": win.agent_steps / win.seconds,
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        checked = checked_steps(win)
        del s0, win, sim                  # the program's state is freed
        checks = check(bench, cell, config, dep, checked, seed)
        result_device = dict(device, memory_peak_bytes=peak)
        metrics = {}
        if trace:
            red = trace_mod.reduce(trace_mod.find_xplane(tmp),
                                   compiled.as_text())
            ctx = Context(reference_params(config, dep), red,
                          device["kind"], seed)
            for m in bench.metrics("per_layer", cell_name):
                value = bench.module("metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result_device.update(busy_s=red.busy_s, window_s=red.window_s)
            out["breakdown"] = red.breakdown()
        else:
            for m in bench.metrics("end_to_end", cell_name):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    correct = out["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return dict(correct=correct, attempted=out["attempted"],
                failed=out["failed"], metrics=metrics, device=result_device,
                **({"breakdown": out["breakdown"]} if trace else {}),
                checks=checks)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        bench = Bench()
        chips = bench.cell(args.workload)["chips"]
        device = require_devices(chips)
    except (OSError, KeyError, ValueError, NoDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start, device)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
