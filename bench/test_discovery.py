"""A configuration, a cell and a per-layer metric added as new files are
found by name and stepped, with no file of the benchmark edited.

The new files go into a temporary copy of ``bench/``; the copy's
``BENCHMARK.json`` gains entries for them, as a later change adds entries.
The run is a traced one on the CPU, so the new metric's reader runs too.
"""

import json
import shutil
import time
from pathlib import Path

from bench import harness
from bench.conftest import CPU

BENCH = Path(__file__).resolve().parent

METRIC = '''"""Device busy time of the traced window (ms)."""


def read(ctx):
    return 1e3 * ctx.reduction.busy_s
'''


def _tree(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_and_stepped(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree(tmp_path / "bench")
    config = json.loads((BENCH / "configs" / "epidemiology.json").read_text())
    config.update(name="tiny-sir")
    config["population"]["agents"] = 2048
    (tmp_path / "bench" / "configs" / "tiny-sir.json").write_text(
        json.dumps(config))
    (tmp_path / "bench" / "workloads" / "tiny-sir.quiet.json").write_text(
        json.dumps({"config": "tiny-sir", "driver": "solo",
                    "episode_steps": 3, "trace_steps": 2,
                    "check": json.loads((BENCH / "workloads" /
                                         "epidemiology-sir.json").read_text()
                                         )["check"]}))
    (tmp_path / "bench" / "metrics" / "device_busy_ms.py").write_text(METRIC)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-sir", "source": "test",
                            "file": "bench/configs/tiny-sir.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-sir.quiet", "config": "tiny-sir",
                              "traffic": "quiet", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "device_busy_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "agent_steps_per_s",
                              "workloads": ["tiny-sir.quiet"]})
    for m in spec["per_layer"]:
        if m["name"] == "device_idle_share":
            m["workloads"].append("tiny-sir.quiet")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(tmp_path / "bench", tmp_path)
    result = harness.run_cell(bench, "tiny-sir.quiet", 2**34 + 9, 0.0, True,
                              time.perf_counter(), dict(CPU))
    assert result["correct"], result["checks"]
    assert result["attempted"] == 2
    assert set(result["metrics"]) == {"device_busy_ms", "device_idle_share"}
    assert result["metrics"]["device_busy_ms"]["value"] > 0
    assert result["device"]["busy_s"] > 0
    assert result["breakdown"]["device_ops"]
    after = _tree(tmp_path / "bench")
    assert all(after[p] == data for p, data in before.items())
