"""JAX's compile phases as telemetry: one ``jax.monitoring`` listener,
registered once per process by :func:`listen`.

JAX reports each phase when it is over. The listener turns the reports into

* process-wide tallies (:func:`tallies`), kept whether or not a session is
  active;
* in the active session, the counters ``jax_compile_events_total{phase}``
  and ``jax_compile_seconds_total{phase}``, and a closed span ``jit.<phase>``
  with the function's name in ``fun``, under the span open when the phase
  ended.

======================  ==========================================  =========
phase                   JAX event                                   span
======================  ==========================================  =========
``trace``               ``/jax/core/compile/jaxpr_trace_duration``  jit.trace
``lower``               ``.../jaxpr_to_mlir_module_duration``       jit.lower
``compile``             ``.../backend_compile_duration``            jit.compile
``cache_load``          ``/jax/compilation_cache/cache_retrieval``  jit.cache_load
                        ``_time_sec`` (a persistent-cache hit's
                        read, inside a ``compile``)
``cache_hit``           ``/jax/compilation_cache/cache_hits``       —
``cache_miss``          ``/jax/compilation_cache/cache_misses``     —
                        (a program written to the persistent cache)
======================  ==========================================  =========

Tracing nests (a jitted function called while another is traced is traced
too), so ``trace`` counts every level. The span bounds are ``time.time()``;
:meth:`SpanTracer.place` puts them on the session's clock, and
``repro.telemetry.profile`` on a profile's.
"""
from __future__ import annotations

import time

PHASES = ("trace", "lower", "compile", "cache_hit", "cache_miss", "cache_load")

_TIMED = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
_COUNTED = {"/jax/compilation_cache/cache_hits": "cache_hit",
            "/jax/compilation_cache/cache_misses": "cache_miss"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_TALLIES = {p: [0, 0.0] for p in PHASES}     # phase -> [events, seconds]
_STATE = {"listening": False, "load": None}  # load: the last cache_load span


def listen() -> None:
    """Register the listener, once per process (imports jax)."""
    if _STATE["listening"]:
        return
    import jax.monitoring as mon
    mon.register_event_time_span_listener(_on_time_span)
    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)
    _STATE["listening"] = True


def tallies() -> dict:
    """``{phase: (events, seconds)}`` since :func:`listen` was first called."""
    return {p: (n, s) for p, (n, s) in _TALLIES.items()}


def _record(phase: str, start: float = None, end: float = None,
            fun: str = None):
    seconds = 0.0 if start is None else end - start
    _TALLIES[phase][0] += 1
    _TALLIES[phase][1] += seconds
    from repro.telemetry import active
    tel = active()
    if tel is None:
        return None
    tel.metrics.counter("jax_compile_events_total", phase=phase).inc()
    if start is None:
        return None
    tel.metrics.counter("jax_compile_seconds_total", phase=phase).inc(seconds)
    return tel.tracer.place(f"jit.{phase}", start, end, fun=fun)


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    phase = _TIMED.get(event)
    if phase is None:
        return
    span = _record(phase, start, end, kw.get("fun_name"))
    if phase == "compile":
        # a cache read is reported before its compile ends, without a name:
        # it is that compile's own
        load, _STATE["load"] = _STATE["load"], None
        if load is not None and span is not None:
            load.attrs["fun"] = span.attrs["fun"]


def _on_event(event: str, **kw) -> None:
    phase = _COUNTED.get(event)
    if phase is not None:
        _record(phase)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == _CACHE_LOAD:
        end = time.time()
        _STATE["load"] = _record("cache_load", end - seconds, end)
