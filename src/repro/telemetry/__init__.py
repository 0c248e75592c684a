"""Opt-in telemetry core: sessions, spans, counters, gauges and events, with
exporters (JSONL / Prometheus text / ASCII dashboard).

It imports nothing of the rest of the repo, so every layer can record into
it: ``repro.mset`` wraps training, estimation and the SPRT in spans, the
fleet layer (``repro.fleet.telemetry``, which re-exports this core and adds
the fleet's metric streams and drift probe) its simulations and tuning.

Telemetry is **off by default**; instrumented code paths are exact no-ops
(bit-identical results, negligible overhead) until a session is opened::

    from repro import telemetry

    with telemetry.session() as tel:
        model = mset.train(X, 4096)
    print(tel.tracer.render())      # span tree, jit.* phases included
    tel.export_jsonl("events.jsonl")

While a session is active:

* :func:`span` also opens a ``jax.profiler.TraceAnnotation`` of the same name
  and attributes, so a span lands in a device trace taken meanwhile;
* JAX's compile phases are counted and placed as ``jit.*`` spans
  (:mod:`repro.telemetry.compiles`), which :mod:`repro.telemetry.profile`
  puts on a device trace's clock.

A span times the host: it closes when the host leaves it, not when the device
work it dispatched ends. Device time comes from the device trace.

Instrumented code calls the module-level helpers (:func:`span`,
:func:`counter`, :func:`gauge`, :func:`event`), which dispatch to the
innermost active session or do nothing. Sessions nest (a scoped probe inside
a long-lived session records to the inner one alone); the stack is
process-global, matching the repo's single-threaded callers.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field

from repro.telemetry import export
from repro.telemetry.compiles import listen
from repro.telemetry.metrics import (
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Series,
    label_str,
)
from repro.telemetry.spans import Span, SpanTracer, render_spans

__all__ = [
    "Telemetry", "session", "active", "span", "counter", "gauge", "event",
    "listen", "MetricsRegistry", "Counter", "Gauge", "Series", "Histogram",
    "DEFAULT_TIME_BUCKETS", "label_str", "Span", "SpanTracer", "render_spans",
    "export",
]


@dataclass
class Telemetry:
    """One telemetry session: a metrics registry + a span tracer + an ad-hoc
    event list, with exporter conveniences."""
    metrics: MetricsRegistry = field(default_factory=MetricsRegistry)
    tracer: SpanTracer = field(default_factory=SpanTracer)
    events: list = field(default_factory=list)

    def event(self, name: str, **fields) -> dict:
        ev = {"name": name, **fields}
        self.events.append(ev)
        return ev

    def export_jsonl(self, path) -> int:
        """Write events + metrics + spans as a JSONL log; returns #lines."""
        return export.write_jsonl(path, registry=self.metrics,
                                  tracer=self.tracer, events=self.events)

    def prometheus(self) -> str:
        return export.prometheus_text(self.metrics)

    def dashboard(self, width: int = 60) -> str:
        return export.dashboard(self.metrics, width=width)


_STACK: list = []


def active() -> Telemetry:
    """The innermost active session, or ``None`` (telemetry disabled)."""
    return _STACK[-1] if _STACK else None


@contextmanager
def session(tel: Telemetry = None):
    """Enable telemetry for the dynamic extent of the block. Yields the
    :class:`Telemetry` session (a fresh one unless ``tel`` is passed)."""
    listen()
    tel = tel if tel is not None else Telemetry()
    _STACK.append(tel)
    try:
        yield tel
    finally:
        _STACK.pop()


@contextmanager
def span(name: str, **attrs):
    """Time a phase in the active session's tracer, and annotate the device
    trace with it; no-op when disabled. Yields the open :class:`Span` (or
    ``None``)."""
    tel = active()
    if tel is None:
        yield None
        return
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(name, **attrs), tel.tracer.span(name, **attrs) as s:
        yield s


def counter(name: str, value: float = 1.0, **labels) -> None:
    """Increment a counter in the active session; no-op when disabled."""
    tel = active()
    if tel is not None:
        tel.metrics.counter(name, **labels).inc(value)


def gauge(name: str, value: float, **labels) -> None:
    """Set a gauge in the active session; no-op when disabled."""
    tel = active()
    if tel is not None:
        tel.metrics.gauge(name, **labels).set(value)


def event(name: str, **fields) -> None:
    """Append an ad-hoc event in the active session; no-op when disabled."""
    tel = active()
    if tel is not None:
        tel.event(name, **fields)
