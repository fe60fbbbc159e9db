# One module per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   Fig 5  breakdown.py      operation runtime shares
#   Fig 6  scaling.py        runtime vs #agents (linearity)
#   Fig 7  cellsort.py       Biocellion cell-sorting model + throughput
#   Fig 9  optimizations.py  progressive optimization speedups
#   Fig 11 neighbor.py       neighbor-search environment comparison
#   Fig 12 sorting.py        sort-frequency study
#   Fig 13 allocator.py      pool allocator vs fresh allocation
#   §4.3   capacity.py       capacity ladder to paper-scale populations
#
# The roofline tables (assignment §Roofline) come from the dry-run
# (`python -m repro.launch.dryrun --all`), not from this harness — this
# container has one CPU core; dry-run numbers are per-device analytic terms.

import sys
import time
import traceback


def main() -> None:
    from repro.launch import compile_cache

    from . import (allocator, breakdown, capacity, cellsort, ensemble,
                   neighbor, optimizations, scaling, sorting)

    compile_cache.enable()

    modules = [("fig5_breakdown", breakdown), ("fig6_scaling", scaling),
               ("fig7_cellsort", cellsort), ("fig9_optimizations", optimizations),
               ("fig11_neighbor", neighbor), ("fig12_sorting", sorting),
               ("fig13_allocator", allocator), ("ladder_capacity", capacity),
               ("ensemble_service", ensemble)]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules:
        if only and only not in name:
            continue
        t0 = time.time()
        try:
            mod.run()
            print(f"# {name} done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
