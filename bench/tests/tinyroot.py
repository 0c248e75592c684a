"""A copy of the benchmark at a size the CPU runs in seconds, for the tests:
the same files and entries, with every configuration and mix cut down."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# gamma: the median-distance heuristic's value on this telemetry at each size
SMALL = {
    "configs/mset2-1024x4096.json": {"n_signals": 32, "n_memvec": 128,
                                     "n_train": 512, "gamma": 8.0},
    "traffic/surveil-b512.json": {"batch": 64, "pool_batches": 8,
                                  "fault_start": 16, "check_batches": 2,
                                  "trace_seconds": 0.5},
}


def edit(path: Path, **changes) -> None:
    d = json.loads(path.read_text())
    d.update(changes)
    path.write_text(json.dumps(d))


def make(dst: Path, sizes: dict = SMALL) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``dst``, cut to ``sizes``."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for rel, changes in sizes.items():
        edit(dst / "bench" / rel, **changes)
    return dst


def run(root: Path, cell: str, capsys, seed: int = 2 ** 33 + 7,
        seconds: float = 0.5, trace: int = 0) -> dict:
    """One run of ``cell`` without the device gate; its result line."""
    from benchlib.harness import main

    capsys.readouterr()
    rc = main(["--workload", cell, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace)], time.perf_counter(),
              root, gate=False, cache=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
