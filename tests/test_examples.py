"""Every example runs, and each one's neighbor sweep streams exactly the
channels pinned for it.

``test_example_runs`` starts each example as its own process, capped by the
``EXAMPLE_*`` variables the examples read, and requires its ``OK:`` line
(each example asserts its own outcome before printing it). The 4-shard
epidemiology run forces four host devices through ``XLA_FLAGS`` in the
child only: the pytest process keeps its one-device view.

``test_example_footprint`` pins each example's realized fused-sweep
footprint (DESIGN.md §3.2): the union of the registered kernels' declared
channel reads, force kernel first, then behaviors in registration order.
``engine.check_kernel_footprints`` also traces every kernel alone with only
its declared channels, catching a read that rides on another kernel's
contribution. The pair-list variant must stream the same channels as the
streamed sweep: the pair list prunes candidates, never channels (§3.4).

``test_chip_smoke_refuses_a_cpu``: ``chip_smoke.py`` must fail on any
platform but a TPU and name the platform it found (README).
"""

import importlib.util
import os
import re
import subprocess
import sys

import pytest

from repro.core import engine as engine_mod
from repro.core.forces import FORCE_READS

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
EXAMPLES = os.path.join(ROOT, "examples")


def _env(**extra) -> dict:
    env = dict(os.environ)
    src = os.path.join(ROOT, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _run(argv, env, timeout=300):
    return subprocess.run([sys.executable, *argv], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


# (example, argv, EXAMPLE_* caps)
RUNS = {
    "quickstart": ("quickstart.py", [], {"EXAMPLE_EPOCHS": "4"}),
    "oncology": ("oncology.py", [], {"EXAMPLE_EPOCHS": "4"}),
    "cell_clustering": ("cell_clustering.py", [],
                        {"EXAMPLE_N": "2000", "EXAMPLE_EPOCHS": "4"}),
    "cell_clustering-pairlist": ("cell_clustering.py", ["--pairlist"],
                                 {"EXAMPLE_N": "2000",
                                  "EXAMPLE_EPOCHS": "4"}),
    "neuroscience": ("neuroscience.py", [], {"EXAMPLE_EPOCHS": "6"}),
    "epidemiology": ("epidemiology.py", [],
                     {"EXAMPLE_N": "6000", "EXAMPLE_EPOCHS": "5"}),
    "epidemiology-distributed": (
        "epidemiology.py", ["--distributed"],
        {"EXAMPLE_N": "6000", "EXAMPLE_EPOCHS": "5",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
    "ensemble_sweep": ("ensemble_sweep.py", [],
                       {"EXAMPLE_N": "200", "EXAMPLE_LANES": "4",
                        "EXAMPLE_POINTS": "8", "EXAMPLE_STEPS": "60"}),
}


@pytest.mark.parametrize("name", list(RUNS))
def test_example_runs(name, tmp_path):
    script, argv, caps = RUNS[name]
    proc = _run([os.path.join(EXAMPLES, script), *argv],
                _env(TMPDIR=str(tmp_path), **caps))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "OK:" in proc.stdout, proc.stdout[-2000:]


def _example(name):
    spec = importlib.util.spec_from_file_location(
        f"example_{name}", os.path.join(EXAMPLES, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# case -> (example, make_config kwargs, expected realized footprint)
FOOTPRINTS = {
    # forces only: GrowDivide/NeuriteGrowth register no neighbor kernels
    "quickstart": ("quickstart", {}, FORCE_READS),
    "oncology": ("oncology", {}, FORCE_READS),
    "neuroscience": ("neuroscience", {}, FORCE_READS),
    # SIR: Infection's kernel, and no diameter — infection never streams
    # mechanical channels
    "epidemiology": ("epidemiology", {}, ("position", "alive", "agent_type")),
    # diffusion-driven: Secretion/Chemotaxis read the substrate, not
    # neighbors — the step runs no neighbor sweep at all
    "cell_clustering": ("cell_clustering", {}, ()),
    "cell_clustering-pairlist": ("cell_clustering", {"pairlist": True},
                                 FORCE_READS),
}


@pytest.mark.parametrize("case", list(FOOTPRINTS))
def test_example_footprint(case):
    name, kwargs, expected = FOOTPRINTS[case]
    mod = _example(name)
    cfg, behaviors = mod.make_config(**kwargs), mod.behaviors()
    assert (cfg.pairlist is not None) == bool(kwargs.get("pairlist"))
    assert engine_mod.realized_footprint(cfg, behaviors) == tuple(expected)
    engine_mod.check_kernel_footprints(cfg, behaviors)


def test_chip_smoke_refuses_a_cpu():
    proc = _run([os.path.join(ROOT, "chip_smoke.py")],
                _env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert re.search(r"no TPU: JAX found \d+ cpu device", proc.stderr), \
        proc.stderr[-2000:]
    assert '"ok": true' not in proc.stdout
