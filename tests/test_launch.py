"""The CLIs' scenarios (launch/simulate.py) and their checkpoint round trips.

``test_scenario_runs``: every scenario of the paper's Table 1 that
``simulate --scenario`` offers is built at a small population and stepped
through ``Simulation.run(check_overflow=True)``, which raises on any
dropped agent or candidate. The scenarios with forces also run on the
Pallas K1 path (interpret mode on a CPU), held to the XLA force path from
the same initial state within chip_smoke.py's tolerance.

``test_scenario_checkpoint_round_trip``: each of those runs' state goes
through ``simcheck.save_state``/``restore_state`` (core/checkpoint.py
underneath), and the next step from the restored state (under the restored
config, which must keep the force path) equals the next step from the
original, bit for bit.

``test_simulate_cli_plain_run``: the unsupervised ``simulate`` loop, which
reports every ``--report-every`` steps.

``test_cli_resume``: the two CLIs' checkpoint/resume round trips as
processes. A checkpoint the first run wrote is set aside and the run
resumes from the one before it: the supervised simulate CLI must write the
same checkpoint again, byte for byte; the service must finish the
requests that were in flight with the same results.
"""

import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import Simulation, checkpoint, restore_state, save_state
from repro.launch import simulate

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
N_AGENTS = 256
STEPS = 3
POS_TOL = 1e-3          # chip_smoke.py's XLA-vs-K1 position tolerance
FORCE_SCENARIOS = ("proliferation", "neuroscience", "oncology")
RUNS = ([(s, "xla") for s in simulate.SCENARIOS]
        + [(s, "pallas") for s in FORCE_SCENARIOS])


def _live_positions(st):
    n = int(st.stats["n_live"])
    alive = np.asarray(st.pool.alive)
    assert n > 0 and alive[:n].all() and not alive[n:].any()
    return np.asarray(st.pool.position)[:n]


@pytest.mark.parametrize("scenario,force_impl", RUNS,
                         ids=[f"{s}-{f}" for s, f in RUNS])
def test_scenario_runs(scenario, force_impl):
    sim, st = simulate.build(scenario, N_AGENTS, force_impl)
    assert sim.config.force_impl == force_impl
    st = sim.run(st, STEPS, check_overflow=True)
    assert int(st.iteration) == STEPS
    pos = _live_positions(st)
    assert np.isfinite(pos).all()
    if force_impl == "pallas":
        ref_sim, ref = simulate.build(scenario, N_AGENTS, "xla")
        ref = ref_sim.run(ref, STEPS, check_overflow=True)
        np.testing.assert_allclose(pos, _live_positions(ref), rtol=0,
                                   atol=POS_TOL)


def _leaves(tree):
    return [np.asarray(jax.random.key_data(x))
            if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)
            else np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("scenario,force_impl", RUNS,
                         ids=[f"{s}-{f}" for s, f in RUNS])
def test_scenario_checkpoint_round_trip(scenario, force_impl, tmp_path):
    sim, st = simulate.build(scenario, N_AGENTS, force_impl)
    st = sim.run(st, 2, check_overflow=True)
    save_state(str(tmp_path), st, sim.config)
    restored, cfg = restore_state(str(tmp_path), sim.config, sim.behaviors)
    assert cfg.force_impl == force_impl
    _assert_bitwise(st, restored)
    resumed = Simulation(cfg, sim.behaviors).step(restored)
    _assert_bitwise(sim.step(st), resumed)


def _cli(tmp_path, module, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    proc = subprocess.run([sys.executable, "-m", module, *args], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    return proc.stdout


def test_simulate_cli_plain_run(tmp_path):
    out = _cli(tmp_path, "repro.launch.simulate", "--scenario",
               "epidemiology", "--agents", "1000", "--iterations", "6",
               "--report-every", "3")
    reports = [l.split() for l in out.splitlines() if l.startswith("iter")]
    assert [r[1] for r in reports] == ["3", "6"], out
    # SIR neither births nor kills: every report counts the whole population
    assert all(r[2] == "n_live=" and r[3] == "1000" for r in reports), out
    assert out.rstrip().endswith("done"), out


def _set_aside_latest(ckpt, dest):
    """Move the newest checkpoint out of ``ckpt``, as if the run had died
    before writing it; return (its step, the step a resume starts from)."""
    steps = checkpoint.list_steps(ckpt)
    assert len(steps) >= 2, steps
    shutil.move(os.path.join(ckpt, f"step_{steps[-1]:09d}"), dest)
    return steps[-1], steps[-2]


def _simulate_resume(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    args = ("--scenario", "oncology", "--agents", "512", "--ckpt-dir", ckpt,
            "--checkpoint-every", "10")
    _cli(tmp_path, "repro.launch.simulate", *args, "--iterations", "20",
         "--supervised")
    dest = str(tmp_path / "set_aside")
    last, start = _set_aside_latest(ckpt, dest)
    out = _cli(tmp_path, "repro.launch.simulate", *args, "--iterations",
               str(last - start), "--resume")
    assert f"at iteration {start}" in out, out
    assert '"completed": true' in out, out
    again = os.path.join(ckpt, f"step_{last:09d}")
    a = np.load(os.path.join(dest, "arrays.npz"))
    b = np.load(os.path.join(again, "arrays.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert a[k].dtype == b[k].dtype, k
        assert a[k].tobytes() == b[k].tobytes(), k


def _sim_serve_resume(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    args = ("--lanes", "4", "--requests", "8", "--agents", "128",
            "--steps", "40", "--ckpt-dir", ckpt, "--checkpoint-every", "20")
    first = _cli(tmp_path, "repro.launch.sim_serve", *args)
    _, start = _set_aside_latest(ckpt, str(tmp_path / "set_aside"))
    out = _cli(tmp_path, "repro.launch.sim_serve", *args, "--resume")
    head = [l for l in out.splitlines() if l.startswith("resumed at tick")]
    assert head and head[0].startswith(f"resumed at tick {start}:"), out
    finished_before = int(head[0].rsplit("finished=", 1)[1])
    results = [l for l in out.splitlines() if l.lstrip().startswith("uid=")]
    assert results and finished_before + len(results) == 8, out
    assert all(r in first.splitlines() for r in results), (first, out)


@pytest.mark.parametrize("round_trip", [_simulate_resume, _sim_serve_resume],
                         ids=["simulate", "sim_serve"])
def test_cli_resume(round_trip, tmp_path):
    round_trip(tmp_path)
