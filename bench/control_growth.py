#!/usr/bin/env python3
"""``bench/control.py`` for the growing spheroid's faults.

    python3 bench/control_growth.py --workload oncology-growth --seeds 3 \
        --control-seeds 3 [--steps 4] [--agents N] [--fault NAME]

The same readings as ``bench/control.py`` (the program's numbers on many
seeds and the bfloat16 control's on the first ``--control-seeds``), with
``--fault`` taken from ``bench/faults_growth.py``: a fault of the contact
forces, K1's column map, division, death or the walk. The benchmark's own
runs never run this.
"""

import argparse
import contextlib
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import control, faults_growth  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--fault", choices=sorted(faults_growth.FAULTS),
                    default=None)
    args = ap.parse_args(argv)
    with (faults_growth.planted(args.fault) if args.fault
          else contextlib.nullcontext()):
        return control.read(args)


if __name__ == "__main__":
    sys.exit(main())
