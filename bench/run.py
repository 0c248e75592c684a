"""On-chip benchmark of the scoping system: runs one cell of BENCHMARK.json.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout on a machine with the chips the cell asks
for; without a TPU it exits non-zero and prints no result. The last line of
standard output is the result as one JSON object (see bench/benchlib/harness.py).
"""
import time

T0 = time.perf_counter()        # process start, for setup_s

import sys                      # noqa: E402
from pathlib import Path        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0, ROOT))
