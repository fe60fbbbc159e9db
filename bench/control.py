#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--steps 4] [--agents N] [--fault NAME]

One process, one compile: for each seed it builds the seed's initial
population, steps the episode ``--steps`` times through the window's own
call (``Simulation.run``), and reads the cell's numbers on the first and the
last step as a run does (``harness.check``). On the first
``--control-seeds`` seeds it also reads the control: the plain reference put
in the program's place, one precision below the configuration's (the
reference module's ``control_numbers``). ``--fault`` plants one of
``bench/faults.py``'s faults in the program first, to read what it gives.
Prints one JSON line per seed (with the episode steps that failed) and a
last line with, per number, the largest program reading, the smallest
control reading and the limit. The benchmark's own runs never run this.
"""

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import deploy, faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None)
    args = ap.parse_args(argv)
    with (faults.planted(args.fault) if args.fault
          else contextlib.nullcontext()):
        return read(args)


def read(args) -> int:
    import jax
    harness.enable_compile_cache()
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    dep = deploy.deployment(config, cell.get("population"), args.agents)
    sim = dep.simulation()
    ref = bench.module("reference", config["reference"])
    params = harness.reference_params(config, dep)
    chk = cell["check"]
    worst, least_control = {}, {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        s0 = jax.block_until_ready(deploy.initial_state(dep, sim, seed))
        win = harness.solo_window(sim, s0, dep.n_agents, cell["episode_steps"],
                                  steps=args.steps)
        failed, failed_at = win.failed, win.failed_at
        steps = harness.checked_steps(win)
        del s0, win
        program = {n: c["value"] for n, c in harness.check(
            bench, cell, config, dep, steps, seed).items()}
        line = {"seed": seed, "failed_steps": failed, "failed_at": failed_at,
                "program": program,
                "seconds": time.perf_counter() - t0}
        for n, v in program.items():
            worst[n] = max(worst.get(n, v), v)
        if k < args.control_seeds:
            control = {}
            for before, _ in steps:
                sample = harness.sample_of(len(before["diameter"]),
                                           chk.get("sample", 0), seed)
                for n, v in ref.control_numbers(before, params, sample, chk,
                                                seed).items():
                    control[n] = max(control.get(n, v), v)
            line["control"] = control
            for n, v in control.items():
                least_control[n] = min(least_control.get(n, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "agents": dep.n_agents,
                      "steps": args.steps, "fault": args.fault, "device":
                      jax.devices()[0].device_kind,
                      "numbers": {n: {"program_max": worst[n],
                                      "control_min": least_control.get(n),
                                      "limit": chk["limits"][n]}
                                  for n in worst}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
