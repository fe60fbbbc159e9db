#!/usr/bin/env python3
"""Chip benchmark of the simulation engine: one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last on standard output (``bench/harness.py``
says what it holds). Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""

import sys
import time

T_START = time.perf_counter()      # set-up is timed from here

from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
