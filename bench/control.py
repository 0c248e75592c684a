"""Reads each cell's control: the plain reference in the precision below the
one the configuration states, put in the program's place, compared with the
reference by the cell's own numbers and limits. The benchmark's runs never
run it; it shows that the check of ``correct`` catches that step down.

    python bench/control.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed: the numbers, their limits and whether the
control would have passed. Runs on whatever device JAX finds.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import harness, spec  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(ROOT, args.workload)
    drv = cell.driver()
    for seed in args.seeds:
        t0 = time.perf_counter()
        passed, checks = harness.judge(drv.control(cell.config, cell.traffic,
                                                   seed), cell.limits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "passed": passed, "checks": checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
