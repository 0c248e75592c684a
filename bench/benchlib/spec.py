"""Finds everything of a cell by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a mix or a metric by name:

* a configuration is the JSON ``file`` that its entry names;
* a traffic mix is ``traffic/<mix>.json``; its ``driver`` key names the
  general driver ``drivers/<driver>.py`` that runs mixes of its kind;
* the limits of a cell's output check are ``limits/<cell>.json``;
* a per-layer metric is read by ``metrics/<metric>.py``, whose ``read(ctx)``
  returns a number, or None where the cell gives it nothing to read.

A later cell, mix, configuration or metric is added as files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = "bench"


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root, name: str):
        self.root = Path(root)
        bench = load_benchmark(self.root)
        self.entry = _by_name(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = _by_name(bench["configs"], self.entry["config"], "config")
        self.config = json.loads((self.root / conf["file"]).read_text())
        bdir = self.root / BENCH_DIR
        self.traffic = json.loads(
            (bdir / "traffic" / f"{self.entry['traffic']}.json").read_text())
        limits = bdir / "limits" / f"{name}.json"
        self.limits = json.loads(limits.read_text()) if limits.is_file() else {}
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]
        self._bdir = bdir

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def driver(self):
        """The driver module that runs this cell's traffic."""
        d = self.traffic["driver"]
        return _module(self._bdir / "drivers" / f"{d}.py", f"bench_driver_{d}")

    def reader(self, metric: str):
        """The reader module of one per-layer metric."""
        return _module(self._bdir / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
