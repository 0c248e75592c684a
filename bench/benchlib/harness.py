"""One run of one cell: set-up, the measured window, the output check, and the
result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the window is measured with the profiler off and the line
carries the cell's end-to-end metrics. With ``--trace 1`` the same window runs
with the profiler off, then a second window of the mix's ``trace_seconds`` (at
most ``--seconds``) is traced, and the line carries the cell's per-layer
metrics, the device's busy and window seconds, and a breakdown: a reader takes
host-clock counts from the first window (``ctx.layer``), which the profiler
does not slow, and device time from the traced one (``ctx.trace`` with the
counts of its window, ``ctx.traced``). Either way the output check runs once the window has closed, the
program's state is freed and the memory peak is read; every number compared
is printed beside its limit, last on standard error and last in the line.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

from jax.profiler import TraceAnnotation

from benchlib import device as devmod
from benchlib import spec, trace

CACHE_DIR = ".jax_cache"       # inside the checkout, at a fixed path


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def judge(checks: list, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): every number at or under its own
    limit, and a limit for every number."""
    out, ok = {}, bool(checks)
    for name, value in checks:
        limit = limits.get(name)
        out[name] = {"value": value, "limit": limit}
        ok = ok and _finite(value) and limit is not None and value <= limit
    return ok, out


def per_layer(cell: spec.Cell, ctx) -> dict:
    units = {}
    for m in cell.per_layer:
        v = cell.reader(m["name"]).read(ctx)
        if v is not None:
            units[m["name"]] = {"value": v, "unit": m["unit"]}
    return units


def measure(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t0: float, device: dict, peaks: dict) -> dict:
    drv = cell.driver().Cell(cell.config, cell.traffic, seed)
    setup_s = time.perf_counter() - t0
    with TraceAnnotation(trace.WINDOW_SPAN):
        drv.run(seconds)
    e2e, layer = drv.end_to_end(), drv.layer()
    if traced:
        got = {}
        window = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        with trace.capture(got):
            with TraceAnnotation(trace.WINDOW_SPAN):
                drv.run(window)
        tr, traced_layer = got["trace"], drv.layer()
    device = dict(device,
                  memory_peak_bytes=devmod.memory_peak_bytes(cell.chips))
    drv.release()
    correct, checks = judge(drv.check(), cell.limits)
    correct = correct and drv.failed == 0

    line = {"correct": correct, "attempted": drv.attempted,
            "failed": drv.failed}
    if traced:
        chips = list(range(cell.chips))
        ctx = SimpleNamespace(trace=tr, chips=chips, peaks=peaks, layer=layer,
                              traced=traced_layer, config=cell.config,
                              traffic=cell.traffic)
        line["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=trace.busy_s(tr, chips), window_s=tr.window_s)
        idle = trace.idle_by_span(tr, 0)
        line["device"] = device
        line["breakdown"] = {
            "device_ops": trace.top_ops(tr, 10),
            "idle_gaps": [[n, s] for n, s in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(e2e, setup_s=setup_s)
        line["metrics"] = {n: {"value": values[n], "unit": u}
                           for n, u in units.items()}
        line["device"] = device
    line["checks"] = checks
    return line


def emit(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv, t0: float, root: Path, gate: bool = True,
         cache: bool = True) -> int:
    args = parse(argv)
    cell = spec.Cell(root, args.workload)
    try:
        if gate:
            device = devmod.gate(cell.chips)
            peaks = devmod.peaks(device["kind"])
        else:
            import jax
            d = jax.devices()
            device = {"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}
            peaks = None
    except devmod.NoDevice as e:
        print(e, file=sys.stderr)
        return 1
    if cache:
        from repro import compile_cache
        compile_cache.use(str(Path(root) / CACHE_DIR))
    line = measure(cell, args.seed, args.seconds, bool(args.trace), t0,
                   device, peaks)
    emit(line)
    return 0
