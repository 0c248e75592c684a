"""The program's own spans and compile counters (``repro.telemetry``) reduced
against a device trace.

A run that opens a telemetry session around set-up and around its traced
window has, besides the harness's ``bench.*`` spans, the program's ``mset.*``
spans in the profile (TraceAnnotations) and JAX's compile phases (``jit.*``)
in the session only, on the session's clock. ``program_spans`` puts both on
the profile's clock; the rest reduces them. Every reduction returns None
where the program recorded nothing to read: a program without the telemetry
core has no session and no such spans.
"""
from __future__ import annotations

from benchlib import trace

PROGRAM_PREFIXES = ("mset.",)
JIT_PREFIX = "jit."


def program_spans(events: list, tracer) -> list:
    """``(name, start_ns, end_ns)`` of the program's annotations in a profile
    (``events``, as ``repro.telemetry.profile.host_events`` reads them) and
    of the session's ``jit.*`` phases placed on the profile's clock by the
    median offset of the spans found in both."""
    from repro.telemetry import profile

    offset = profile.clock_offset_ns(tracer, events)
    if offset is None:
        return list(events)
    jit = [(path.rsplit("/", 1)[-1], int(s), int(e))
           for path, s, e in profile.placed(tracer, offset)
           if path.rsplit("/", 1)[-1].startswith(JIT_PREFIX)]
    return sorted(list(events) + jit, key=lambda ev: ev[1])


def idle_by_span(tr: trace.Trace, spans: list, chip: int = 0) -> dict:
    """Idle seconds of one chip by the innermost harness, program or
    ``jit.*`` span open over them."""
    both = trace.Trace(ops=tr.ops, spans=tr.spans + spans, window=tr.window)
    return trace.idle_by_span(both, chip)


def idle_compile_share(tr: trace.Trace, spans: list, chips):
    """% of the window in which a chip is idle while the host is inside a
    ``jit.*`` span, averaged over ``chips``; None without such a span."""
    jit = trace.union(trace.clip([(s, e) for n, s, e in spans
                                  if n.startswith(JIT_PREFIX)], *tr.window))
    if not jit or tr.window_s <= 0:
        return None
    idle = sum(max(0, min(ge, je) - max(gs, js))
               for c in chips for gs, ge in trace.gaps(tr, c)
               for js, je in jit)
    return 100.0 * idle * 1e-9 / (tr.window_s * len(chips))


def lowerings_per_batch(counters, calls: int):
    """Lowerings (``jax_compile_events_total{phase=lower}``) of the traced
    window's session over its batches: 0 where the session counted none;
    None without a session or a batch."""
    if counters is None or not calls:
        return None
    lowered = counters.get("jax_compile_events_total", {}).get("phase=lower")
    return (lowered or 0.0) / calls


def span_s(tracer, name: str):
    """Seconds of every span named ``name`` in a session; None where the
    session has none (or there is no session)."""
    if tracer is None or tracer.find(name) is None:
        return None
    return tracer.total(name)


def setup_spans(tracer, k: int = 10) -> list:
    """The ``k`` longest entries of the set-up span tree, two levels deep:
    ``[path, seconds]`` summed over spans of one path."""
    if tracer is None:
        return []
    acc = {}
    for root in tracer.roots:
        for s, depth, path in root.walk():
            if depth <= 1 and s.duration_s is not None:
                acc[path] = acc.get(path, 0.0) + s.duration_s
    return [[p, v] for p, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
