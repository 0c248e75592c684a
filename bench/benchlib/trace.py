"""Profiler traces: capture one window, and reduce it to device busy time,
per-operation time and idle gaps attributed to the benchmark's host spans.

The reduction works on plain interval lists, so it is tested without a chip.
``load`` reads the ``.xplane.pb`` the JAX profiler writes: device operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane, host
spans the ``bench.*`` annotations the harness writes around each call into a
layer, and the traced window is the ``bench.window`` span.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

DEVICE_PLANE = r"^/device:TPU:(\d+)$"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    chip: int
    name: str        # the op's own name: the HLO instruction's, e.g. %fusion.2
    module: str      # its XLA module (program), e.g. jit_estimate
    text: str        # module, the op's full text and string stats, to match
    start: int       # ns
    end: int         # ns


@dataclass
class Trace:
    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)     # (name, start_ns, end_ns)
    window: tuple = (0, 0)                        # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_intervals(tr: Trace, chip: int) -> list:
    return union(clip([(op.start, op.end) for op in tr.ops if op.chip == chip],
                      *tr.window))


def busy_s(tr: Trace, chips) -> float:
    """Seconds in which some operation ran, averaged over ``chips``."""
    total = sum(e - s for c in chips for s, e in busy_intervals(tr, c))
    return total * 1e-9 / max(len(chips), 1)


def gaps(tr: Trace, chip: int) -> list:
    """The idle intervals of one chip inside the window."""
    lo, hi = tr.window
    out, t = [], lo
    for s, e in busy_intervals(tr, chip):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(tr: Trace, t: float) -> str:
    """The innermost harness span (other than the window) open at ``t``."""
    best = None
    for name, s, e in tr.spans:
        if name != WINDOW_SPAN and s <= t < e and (best is None
                                                   or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "(between spans)"


def idle_by_span(tr: Trace, chip: int) -> dict:
    """Idle seconds of one chip by what the host was doing: each gap is cut
    at the span boundaries inside it, and each piece goes to the innermost
    span open over it."""
    cuts = sorted({t for _, s, e in tr.spans for t in (s, e)})
    out = {}
    for s, e in gaps(tr, chip):
        points = [s] + [c for c in cuts if s < c < e] + [e]
        for a, b in zip(points, points[1:]):
            name = span_at(tr, (a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def op_durations(tr: Trace, pattern: str, chip: int = 0) -> list:
    """Seconds of each op of one chip that matches ``pattern`` and starts
    inside the window, whole."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return [(op.end - op.start) * 1e-9 for op in tr.ops
            if op.chip == chip and lo <= op.start < hi and rx.search(op.text)]


def op_busy_s(tr: Trace, pattern: str, chip: int = 0) -> float:
    """Seconds in which some op of one chip that matches ``pattern`` ran,
    inside the window: the union, since a loop's body ops nest inside it."""
    rx = re.compile(pattern)
    return sum(e - s for s, e in union(clip(
        [(op.start, op.end) for op in tr.ops
         if op.chip == chip and rx.search(op.text)], *tr.window))) * 1e-9


def top_ops(tr: Trace, k: int = 10, chip: int = 0) -> list:
    """The ``k`` operations that took most device time, by module and name."""
    lo, hi = tr.window
    acc = {}
    for op in tr.ops:
        if op.chip == chip and op.end > lo and op.start < hi:
            key = f"{op.module}/{op.name}" if op.module else op.name
            acc[key] = acc.get(key, 0.0) + (min(op.end, hi)
                                            - max(op.start, lo)) * 1e-9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def _stat_text(stats) -> dict:
    out = {}
    for key, value in stats:
        if isinstance(value, bytes):
            value = value.decode(errors="replace")
        if isinstance(value, str):
            out[key] = value
    return out


def _module_of(modules: list, starts: list, t: int) -> str:
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and modules[k][0] <= t <= modules[k][1]:
        return modules[k][2]
    return ""


def load(path: str, device_plane: str = DEVICE_PLANE,
         op_line: str = OP_LINE) -> Trace:
    """Device ops and harness spans from one ``.xplane.pb``. An op's module
    is the ``XLA Modules`` event of its plane that holds its start; its name
    is the HLO text up to `` = ``."""
    from jax.profiler import ProfileData

    rx = re.compile(device_plane)
    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = rx.match(plane.name)
        lines = list(plane.lines)
        modules = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                          ev.name.split("(")[0])
                         for line in lines if m and line.name == MODULE_LINE
                         for ev in line.events)
        starts = [mod[0] for mod in modules]
        for line in lines:
            if m and line.name == op_line:
                chip = int(m.group(1)) if m.groups() else 0
                for ev in line.events:
                    start = int(ev.start_ns)
                    module = _module_of(modules, starts, start)
                    st = _stat_text(ev.stats)
                    text = " ".join([module, ev.name] + list(st.values()))
                    tr.ops.append(Op(chip, ev.name.split(" = ")[0], module,
                                     text, start,
                                     start + int(ev.duration_ns)))
            elif not (m and line.name == MODULE_LINE):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        tr.spans.append((ev.name, start,
                                         start + int(ev.duration_ns)))
    windows = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if windows:
        tr.window = (min(s for s, _ in windows), max(e for _, e in windows))
    return tr


@contextlib.contextmanager
def capture(out: dict):
    """Trace the block; on exit ``out["trace"]`` holds the loaded ``Trace``.
    The raw profile goes to a temporary directory under ``TMPDIR`` and is
    deleted once read."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        out["trace"] = load(paths[0]) if paths else Trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
