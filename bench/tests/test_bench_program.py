"""The program's spans and compile counters against a device trace: the
alignment of the session's clock to the profile's, the idle share spent
compiling, the lowering count, and None where the program recorded nothing."""
import tinyroot  # noqa: F401

import pytest

from benchlib import program
from benchlib.trace import Op, Trace
from repro.telemetry import SpanTracer

MS = 1_000_000      # ns


def session_tracer(offset_s: float):
    """A session whose clock runs ``offset_s`` behind the profile's: two
    batches of ``mset.sprt``, the scan starting 1 and 2 ms in, each with a
    lowering of 4 ms placed after the fact, as JAX reports it."""
    import time

    clock = [0.0]
    tr = SpanTracer(clock=lambda: clock[0])
    for b in range(2):
        clock[0] = 0.010 * b - offset_s
        with tr.span("mset.sprt"):
            clock[0] += 0.001 * (1 + b)
            with tr.span("mset.sprt.scan"):
                # time.time() of the moment 1 ms into the scan
                lo = time.time() + 0.001
                tr.place("jit.lower", lo, lo + 0.004, fun="jit(scan)")
                clock[0] += 0.006
            clock[0] += 0.001
    return tr


def profile_events():
    """The profile began in the second batch, at 10 ms on its clock."""
    return [("mset.sprt", 10 * MS, 19 * MS),
            ("mset.sprt.scan", 12 * MS, 18 * MS)]


def test_alignment_places_jit_phases_on_the_profile_clock():
    tracer = session_tracer(offset_s=5.0)
    spans = program.program_spans(profile_events(), tracer)
    lowers = [(s, e) for n, s, e in spans if n == "jit.lower"]
    # the second batch's lowering at 13-17 ms; the first's before the profile
    assert lowers[-1] == (pytest.approx(13 * MS, abs=50_000),
                          pytest.approx(17 * MS, abs=50_000))
    assert [n for n, _, _ in spans].count("mset.sprt.scan") == 1


def test_alignment_without_a_common_span_keeps_the_annotations():
    tracer = session_tracer(offset_s=5.0)
    events = [("mset.estimate", 0, MS)]
    assert program.program_spans(events, tracer) == events


def window_trace():
    """A 20 ms window: the device busy 0-2 and 17-18 ms; bench.sprt 10-19."""
    ops = [Op(0, "%fusion.1", "jit_estimate", "", 0, 2 * MS),
           Op(0, "%while.2", "jit_scan", "", 17 * MS, 18 * MS)]
    spans = [("bench.window", 0, 20 * MS), ("bench.sprt", 10 * MS, 19 * MS)]
    return Trace(ops=ops, spans=spans, window=(0, 20 * MS))


def test_idle_compile_share_on_a_hand_built_window():
    # idle 2-17 and 18-20; the host lowers 12-16 and traces 15-18
    spans = [("mset.sprt.scan", 11 * MS, 18 * MS),
             ("jit.lower", 12 * MS, 16 * MS), ("jit.trace", 15 * MS, 18 * MS)]
    share = program.idle_compile_share(window_trace(), spans, [0])
    # idle under a jit span: 12-17 = 5 ms of 20
    assert share == pytest.approx(25.0)
    idle = program.idle_by_span(window_trace(), spans, 0)
    assert idle["jit.lower"] == pytest.approx(0.003)      # 12-15
    assert idle["jit.trace"] == pytest.approx(0.002)      # 15-17
    assert idle["mset.sprt.scan"] == pytest.approx(0.001)  # 11-12
    assert idle["bench.sprt"] == pytest.approx(0.002)      # 10-11, 18-19


def test_lowering_count_per_batch():
    counters = {"jax_compile_events_total": {"phase=lower": 40.0,
                                             "phase=trace": 560.0}}
    assert program.lowerings_per_batch(counters, 40) == pytest.approx(1.0)
    # a session that lowered nothing reads 0, not nothing
    assert program.lowerings_per_batch({}, 40) == 0.0


def test_setup_spans_and_span_seconds():
    tracer = session_tracer(offset_s=0.0)
    top = program.setup_spans(tracer, k=2)
    assert top[0] == ["mset.sprt", pytest.approx(0.017)]
    assert top[1] == ["mset.sprt/mset.sprt.scan", pytest.approx(0.012)]
    assert program.span_s(tracer, "jit.lower") == pytest.approx(0.008,
                                                                 abs=1e-6)


def test_readers_return_none_where_nothing_was_recorded():
    tracer = session_tracer(offset_s=0.0)
    spans = [("mset.sprt.scan", 11 * MS, 18 * MS)]
    assert program.idle_compile_share(window_trace(), spans, [0]) is None
    assert program.lowerings_per_batch(None, 40) is None
    assert program.lowerings_per_batch({}, 0) is None
    assert program.span_s(tracer, "mset.train.eigh") is None
    assert program.span_s(None, "mset.train.eigh") is None
    assert program.setup_spans(None) == []
