"""A session's spans on the clock of a ``jax.profiler`` trace.

Spans opened with :func:`repro.telemetry.span` while the profiler runs are in
its trace as TraceAnnotations of the same name; the phases JAX reports after
the fact (``jit.*``) are only in the session. The profile's host events count
from the profile's own zero, so the session's clock is mapped onto it by the
offset between a span's start and its annotation's, taken over the spans
found in both.
"""
from __future__ import annotations

import numpy as np


def host_events(path: str, prefixes: tuple) -> list:
    """``(name, start_ns, end_ns)`` of the events of an ``.xplane.pb`` whose
    names start with one of ``prefixes``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    start = int(ev.start_ns)
                    out.append((ev.name, start, start + int(ev.duration_ns)))
    return sorted(out, key=lambda e: e[1])


def clock_offset_ns(tracer, events: list, tol_ns: float = 100_000):
    """Profile time minus session time, in ns; None where no span name is in
    both. Each pairing of a span with an annotation of its name proposes an
    offset. The true one is proposed by every span the profile saw, to within
    the microseconds that part the two clocks' readings; the rest scatter by
    the spacing of the calls. So the offset is the median of the densest
    cluster of proposals ``tol_ns`` wide."""
    starts = {}
    for name, start, _ in events:
        starts.setdefault(name, []).append(start)
    proposals = [e - s.t0 * 1e9 for root in tracer.roots
                 for s, _, _ in root.walk() for e in starts.get(s.name, ())]
    if not proposals:
        return None
    c = np.sort(proposals)
    ends = np.searchsorted(c, c + tol_ns, side="right")
    i = int(np.argmax(ends - np.arange(len(c))))
    return float(np.median(c[i:ends[i]]))


def placed(tracer, offset_ns: float) -> list:
    """``(path, start_ns, end_ns)`` of every closed span of ``tracer`` on the
    profile's clock; ``path`` joins the names from the root with ``/``."""
    return [(path, s.t0 * 1e9 + offset_ns,
             (s.t0 + s.duration_s) * 1e9 + offset_ns)
            for root in tracer.roots for s, _, path in root.walk()
            if s.duration_s is not None]
