"""The command refuses to run without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ARGS = ["--workload", "epidemiology-sir", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            continue
    return False


def test_no_tpu_exits_nonzero_naming_the_platform():
    proc = _run(BENCH.parent)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    assert not _has_result(proc.stdout)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
