"""Fleet telemetry layer: the metrics registry and span tracer, the opt-in
contract (off by default, bit-identical results, no-op helpers), stream
determinism and numpy==jax stream equality, the exporters (JSONL / Prometheus
text / ASCII dashboard), and the MSET+SPRT drift probe's headline behaviour —
quiet on a fresh baseline replicate, alarmed on an injected service-time
degradation."""
import json

import numpy as np
import pytest

from repro.core import CellResult, RooflineTerms, get_shape
from repro.fleet import (FleetConfig, Objective, PoolConfig, PredictivePolicy,
                         QueueProportionalPolicy, TuningBudget, diurnal_trace,
                         flash_crowd_trace, load_trace_csv, mset_scenario,
                         poisson_trace, service_model_from_cell, simulate,
                         simulate_fleet, telemetry, telemetry_dashboard,
                         tune, tuning_scenario)
from repro.fleet.telemetry import (MetricsRegistry, SpanTracer, export,
                                   record_sim, render_spans)

# bin-by-bin SimResult fields the off-vs-on runs must match byte for byte
BITEXACT_FIELDS = ("served", "queue", "billed_replicas", "latency_s",
                   "ok_served", "utilization", "dropped", "admitted",
                   "replicas", "pool_billed", "pool_served", "pool_replicas")


def _cell(shape="v5e-4", t_comp=0.4, t_mem=0.1, t_coll=0.05, batch=64):
    return CellResult(params={"batch": batch,
                              "chips": get_shape(shape).chips},
                      shape_name=shape,
                      terms=RooflineTerms(t_comp, t_mem, t_coll),
                      analysis={"peak_memory_per_device": 1e9})


def _service(**kw):
    return service_model_from_cell(_cell(**kw),
                                   units_per_step=kw.get("batch", 64))


def _sim(seed=0, n_seeds=3, backend="numpy"):
    svc = _service()
    tr = flash_crowd_trace(4 * svc.max_throughput, 900.0, dt_s=5.0,
                           n_seeds=n_seeds, seed=seed)
    return simulate(tr, svc, QueueProportionalPolicy(), slo_s=2.0,
                    cold_start_s=30.0, backend=backend)


# ----------------------- registry instruments -------------------------------

def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    c = reg.counter("fleet_served_total", cls="interactive")
    c.inc(3)
    assert reg.counter("fleet_served_total", cls="interactive") is c
    assert reg.counter("fleet_served_total", cls="batch") is not c
    assert c.value == 3.0
    reg.gauge("fleet_depth").set(7.0)
    with pytest.raises(ValueError, match="already registered"):
        reg.series("fleet_depth")
    snap = reg.snapshot()
    assert snap["counter"]["fleet_served_total"]["cls=interactive"] == 3.0
    assert snap["gauge"]["fleet_depth"][""] == 7.0


def test_histogram_buckets_quantiles_and_weights():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, float("inf")))
    h.observe([0.05, 0.5, 2.0], weights=[1.0, 2.0, 1.0])
    h.observe([0.5], weights=[0.0])            # zero weight: dropped
    np.testing.assert_allclose(h.counts, [1.0, 2.0, 1.0])
    assert h.count == 4.0
    assert h.sum == pytest.approx(0.05 + 1.0 + 2.0)
    assert h.quantile(0.5) == 1.0              # covering-bucket upper bound
    assert h.quantile(0.99) == float("inf")
    with pytest.raises(ValueError, match="sorted"):
        reg.histogram("bad_seconds", buckets=(1.0, 0.1, float("inf")))
    with pytest.raises(ValueError, match="inf"):
        reg.histogram("bad2_seconds", buckets=(0.1, 1.0))


def test_span_tracer_nesting_and_render():
    fake = iter(np.arange(0.0, 10.0, 0.5))
    tr = SpanTracer(clock=lambda: float(next(fake)))
    with tr.span("tune", scenario="flash"):
        with tr.span("tune.sample"):
            pass
        with tr.span("tune.race", rounds=3):
            with tr.span("jaxsim.dispatch", kind="cold"):
                pass
    assert len(tr.roots) == 1
    root = tr.roots[0]
    assert [c.name for c in root.children] == ["tune.sample", "tune.race"]
    assert root.duration_s > 0
    assert root.find("jaxsim.dispatch").attrs["kind"] == "cold"
    text = render_spans(tr.roots)
    for name in ("tune", "tune.sample", "tune.race", "jaxsim.dispatch"):
        assert name in text
    events = tr.to_events()
    paths = {e["path"] for e in events}
    assert "tune/tune.race/jaxsim.dispatch" in paths
    assert all(e["type"] == "span" for e in events)


# ----------------------- opt-in contract ------------------------------------

def test_helpers_are_noops_without_session():
    assert telemetry.active() is None
    with telemetry.span("anything", k=1) as s:
        assert s is None
    telemetry.counter("nope_total")
    telemetry.gauge("nope", 1.0)
    telemetry.event("nope")
    assert telemetry.active() is None


def test_session_nesting_records_to_innermost():
    with telemetry.session() as outer:
        telemetry.counter("outer_total")
        with telemetry.session() as inner:
            telemetry.counter("inner_total")
            assert telemetry.active() is inner
        assert telemetry.active() is outer
    assert outer.metrics.get("outer_total") is not None
    assert outer.metrics.get("inner_total") is None
    assert inner.metrics.get("inner_total").value == 1.0
    assert telemetry.active() is None


def test_disabled_session_is_bit_exact_per_backend():
    """Running under a telemetry session must not perturb results: the hook
    only reads the assembled SimResult."""
    for backend in ("numpy", "jax"):
        if backend == "jax":
            pytest.importorskip("jax")
        off = _sim(backend=backend)
        with telemetry.session():
            on = _sim(backend=backend)
        for k in BITEXACT_FIELDS:
            assert np.array_equal(getattr(off, k), getattr(on, k)), \
                f"{backend}: field {k!r} changed under telemetry"


def test_tune_output_identical_with_and_without_session():
    scn = mset_scenario(n_signals=256, n_memvec=512, fleet=1, slo_s=1.0)
    svc = scn.service_for(scn.cheapest_shape())
    tr = flash_crowd_trace(3.5 * svc.max_throughput, 900.0, dt_s=5.0,
                           n_seeds=3, seed=2)
    obj = Objective(min_attainment=1.0, penalty_usd_per_hour=1e5)
    budget = TuningBudget(n_candidates=6)
    space = PredictivePolicy.param_space()

    def run():
        ts = tuning_scenario(scn, tr, PredictivePolicy, cold_start_s=30.0,
                             backend="numpy")
        return tune(ts, space, obj, budget, seed=0)

    off = run()
    with telemetry.session() as tel:
        on = run()
    assert off.winner.params == on.winner.params
    np.testing.assert_array_equal(off.winner.score, on.winner.score)
    assert off.sims_used == on.sims_used
    # spans land on the report only when a session was active
    assert off.spans is None and off.timing_breakdown() == ""
    assert on.spans is not None and "tune.race" in on.timing_breakdown()
    assert "timing breakdown" in on.summary()
    assert tel.metrics.get("tuning_sims_total", backend="numpy") is not None


# ----------------------- stream determinism + backend equality --------------

def _snapshot_allclose(a: dict, b: dict, atol=1e-8):
    assert set(a["counter"]) == set(b["counter"])
    for name, slots in a["counter"].items():
        assert set(slots) == set(b["counter"][name]), name
        for ls, v in slots.items():
            assert v == pytest.approx(b["counter"][name][ls], abs=atol), \
                f"counter {name}{{{ls}}}"
    assert set(a["series"]) == set(b["series"])
    for name, slots in a["series"].items():
        for ls, vals in slots.items():
            np.testing.assert_allclose(vals, b["series"][name][ls],
                                       atol=atol, rtol=1e-9,
                                       err_msg=f"series {name}{{{ls}}}")
    assert set(a["histogram"]) == set(b["histogram"])
    for name, slots in a["histogram"].items():
        for ls, h in slots.items():
            np.testing.assert_allclose(h["counts"],
                                       b["histogram"][name][ls]["counts"],
                                       atol=atol,
                                       err_msg=f"histogram {name}{{{ls}}}")


def test_streams_deterministic_across_runs():
    snaps = []
    for _ in range(2):
        with telemetry.session() as tel:
            _sim()
        snaps.append(tel.metrics.snapshot())
    assert snaps[0] == snaps[1]


def test_numpy_and_jax_emit_equal_streams():
    pytest.importorskip("jax")
    snaps = {}
    for backend in ("numpy", "jax"):
        with telemetry.session() as tel:
            _sim(backend=backend)
        snaps[backend] = tel.metrics.snapshot()
    # the jax path additionally counts its dispatch/cache metrics; restrict
    # the comparison to the record_sim catalog both backends share
    jax_only = ("jaxsim_dispatch_total", "jaxsim_dispatch_seconds_total",
                "jaxsim_core_cache_total", "fleet_kernel_cache_total")
    for snap in snaps.values():
        for kind in snap:
            for name in [n for n in snap[kind] if n in jax_only]:
                del snap[kind][name]
    _snapshot_allclose(snaps["numpy"], snaps["jax"])


def test_backend_stream_equality_property():
    pytest.importorskip("jax")
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    svc = _service()

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=500),
           rate_mult=st.floats(min_value=1.0, max_value=5.0))
    def prop(seed, rate_mult):
        # fixed (T, C, P) so the compiled program is traced once; rates and
        # seeds are data
        tr = poisson_trace(rate_mult * svc.max_throughput, 600.0, dt_s=5.0,
                           n_seeds=2, seed=seed)
        snaps = {}
        for backend in ("numpy", "jax"):
            with telemetry.session() as tel:
                simulate(tr, svc, QueueProportionalPolicy(), slo_s=2.0,
                         cold_start_s=30.0, backend=backend)
            snaps[backend] = tel.metrics.snapshot()
        for name in ("fleet_service_time_s", "fleet_utilization",
                     "fleet_arrival_rate"):
            np.testing.assert_allclose(
                snaps["numpy"]["series"][name][""],
                snaps["jax"]["series"][name][""],
                atol=1e-8, rtol=1e-9, err_msg=name)
        np.testing.assert_allclose(
            snaps["numpy"]["histogram"]["fleet_sojourn_seconds"]
            ["cls=default"]["counts"],
            snaps["jax"]["histogram"]["fleet_sojourn_seconds"]
            ["cls=default"]["counts"], atol=1e-8)

    prop()


def test_jax_backend_emits_cache_and_dispatch_metrics():
    pytest.importorskip("jax")
    with telemetry.session() as tel:
        _sim(backend="jax")
        _sim(backend="jax")
    snap = tel.metrics.snapshot()
    disp = snap["counter"]["jaxsim_dispatch_total"]
    assert sum(disp.values()) == 2.0
    secs = snap["counter"]["jaxsim_dispatch_seconds_total"]
    assert all(v >= 0.0 for v in secs.values())
    core = snap["counter"]["jaxsim_core_cache_total"]
    assert sum(core.values()) == 2.0
    # the second identical run must reuse the cached jit program
    assert core.get("result=hit", 0.0) >= 1.0


# ----------------------- exporters ------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    record_sim(reg, _sim())
    text = export.prometheus_text(reg)
    assert "# TYPE fleet_served_total counter" in text
    assert "# TYPE fleet_sojourn_seconds histogram" in text
    assert 'fleet_sojourn_seconds_bucket{cls="default",le="+Inf"}' in text
    assert "fleet_sojourn_seconds_count" in text
    assert "# TYPE fleet_utilization gauge" in text  # series: last value
    assert "fleet_utilization_bins" in text
    # every non-comment line is "name{labels} number"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        assert name and (val in ("NaN", "+Inf", "-Inf")
                         or float(val) == float(val))


def test_jsonl_export_round_trips(tmp_path):
    with telemetry.session() as tel:
        with telemetry.span("outer", k=1):
            _sim()
        telemetry.event("marker", note="hello")
    path = tmp_path / "events.jsonl"
    n = tel.export_jsonl(path)
    lines = path.read_text().strip().splitlines()
    assert len(lines) == n > 0
    records = [json.loads(ln) for ln in lines]
    kinds = {r["type"] for r in records}
    assert {"event", "counter", "series", "histogram", "span"} <= kinds
    assert records[0] == {"type": "event", "name": "marker", "note": "hello"}
    spans = [r for r in records if r["type"] == "span"]
    assert any(s["name"] == "outer" and s["attr_k"] == 1 for s in spans)


def test_sparkline_and_dashboard():
    assert export.sparkline([]) == ""
    assert len(export.sparkline(np.arange(200.0), width=40)) == 40
    flat = export.sparkline([5.0, 5.0, 5.0])
    assert len(set(flat)) == 1
    ramp = export.sparkline([0.0, 1.0, 2.0, 3.0])
    assert ramp[0] != ramp[-1]
    with telemetry.session() as tel:
        _sim()
    dash = tel.dashboard(width=40)
    assert "fleet_service_time_s" in dash
    assert "fleet_sim_runs_total" in dash
    assert "fleet_sojourn_seconds" in dash


def test_report_telemetry_dashboard_on_bare_result():
    dash = telemetry_dashboard(_sim(), width=40)
    assert "fleet_utilization" in dash
    assert "policy=queue_prop" in dash or "fleet_sim_runs_total" in dash


# ----------------------- trace-ingest event ---------------------------------

def test_load_trace_csv_emits_event(tmp_path):
    p = tmp_path / "trace.csv"
    p.write_text("# recorded rates\ntimestamp,rate\n0,10\n60,30\n120,20\n")
    with telemetry.session() as tel:
        tr = load_trace_csv(p, rate_col="rate", dt_s=60.0,
                            mean_rate_per_s=40.0, n_seeds=2)
    assert tr.n_bins == 3
    evs = [e for e in tel.events if e["name"] == "trace_csv_loaded"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["rows"] == 3
    assert ev["skipped_rows"] == 2          # comment + header
    assert ev["rescale_factor"] == pytest.approx(2.0)   # mean 20 -> 40
    assert ev["mean_rate_per_s"] == pytest.approx(40.0)


# ----------------------- drift probe ----------------------------------------

@pytest.fixture(scope="module")
def drift_setup():
    pytest.importorskip("jax")
    from repro.fleet.telemetry import DriftProbe

    svc = _service()
    fleet = FleetConfig((PoolConfig(svc, cold_start_s=30.0),))

    def run_trace(seed, fl=fleet):
        tr = diurnal_trace(2.0 * svc.max_throughput, 3600.0, dt_s=10.0,
                           n_seeds=6, seed=seed)
        return simulate_fleet(tr, fl, QueueProportionalPolicy(), slo_s=2.0)

    probe = DriftProbe().fit(run_trace(0))
    return probe, fleet, run_trace


def test_drift_probe_quiet_on_fresh_baseline(drift_setup):
    probe, _, run_trace = drift_setup
    rep = probe.check(run_trace(7))
    assert not rep.drifted
    assert rep.alarm_bins < probe.min_alarm_bins
    assert "[ok]" in rep.summary()


def test_drift_probe_flags_degraded_service(drift_setup):
    from repro.fleet.telemetry import degrade_fleet

    probe, fleet, run_trace = drift_setup
    rep = probe.check(run_trace(7, fl=degrade_fleet(fleet, 1.3)))
    assert rep.drifted
    assert rep.first_alarm_bin >= 0
    assert rep.alarm_bins > rep.n_bins // 2     # sustained, not a blip
    assert "[DRIFT]" in rep.summary()
    assert rep.per_signal_alarms["service_time_s"] > 0


def test_drift_probe_emits_telemetry_and_validates(drift_setup):
    from repro.fleet.telemetry import telemetry_matrix

    probe, _, run_trace = drift_setup
    sim = run_trace(11)
    X = telemetry_matrix(sim)
    assert X.shape == (sim.arrivals.shape[1], 3)
    with pytest.raises(ValueError, match="unknown drift signal"):
        telemetry_matrix(sim, signals=("bogus",))
    with telemetry.session() as tel:
        rep = probe.check(X)                    # raw-matrix path
    assert not rep.drifted
    snap = tel.metrics.snapshot()
    assert snap["counter"]["fleet_drift_checks_total"]["verdict=ok"] == 1.0
    assert any(e["name"] == "drift_check" for e in tel.events)


def test_degrade_fleet_identity_and_scaling():
    from repro.fleet.telemetry import degrade_fleet

    svc = _service()
    fleet = FleetConfig((PoolConfig(svc, cold_start_s=30.0),))
    same = degrade_fleet(fleet, 1.0)
    assert same.pools[0].service.t_fixed == svc.t_fixed
    slow = degrade_fleet(fleet, 1.5)
    assert slow.pools[0].service.t_fixed == pytest.approx(1.5 * svc.t_fixed)
    assert slow.pools[0].service.t_per_unit == \
        pytest.approx(1.5 * svc.t_per_unit)
    # original untouched (frozen dataclasses are replaced, not mutated)
    assert fleet.pools[0].service.t_fixed == svc.t_fixed


# ----------------------- telemetry core: device trace and compile phases ----

def test_spans_annotate_the_device_trace_only_in_a_session(monkeypatch):
    """No session: a span opens no TraceAnnotation. In a session it opens one
    of the same name and attributes."""
    jax = pytest.importorskip("jax")
    opened = []

    class Recorder:
        def __init__(self, name, **attrs):
            opened.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with telemetry.span("mset.sprt", k=1) as s:
        assert s is None
    assert opened == []
    with telemetry.session() as tel:
        with telemetry.span("mset.sprt", k=1):
            pass
    assert opened == [("mset.sprt", {"k": 1})]
    assert tel.tracer.roots[0].name == "mset.sprt"


def test_fresh_jit_counts_one_lowering_and_a_cached_call_none():
    jax = pytest.importorskip("jax")
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.arange(5.0, dtype=np.float32)
    with telemetry.session() as tel:
        f(x)
    first = tel.metrics.snapshot()["counter"]
    assert first["jax_compile_events_total"]["phase=lower"] == 1.0
    assert first["jax_compile_seconds_total"]["phase=lower"] > 0.0
    lower = [s for s in tel.tracer.roots if s.name == "jit.lower"]
    assert len(lower) == 1 and lower[0].duration_s > 0.0
    assert "fun" in lower[0].attrs
    with telemetry.session() as again:
        f(x)
    snap = again.metrics.snapshot()["counter"]
    assert "phase=lower" not in snap.get("jax_compile_events_total", {})


def test_placed_phases_nest_and_take_the_open_span_as_parent():
    import time

    tr = SpanTracer()
    now = time.time()
    with tr.span("mset.sprt.scan"):
        tr.place("jit.trace", now - 0.004, now - 0.003, fun="inner")
        tr.place("jit.trace", now - 0.005, now - 0.002, fun="outer")
        tr.place("jit.lower", now - 0.002, now - 0.001, fun="jit(scan)")
    scan = tr.roots[0]
    assert [c.name for c in scan.children] == ["jit.trace", "jit.lower"]
    outer = scan.children[0]
    assert outer.attrs["fun"] == "outer"
    assert [c.attrs["fun"] for c in outer.children] == ["inner"]
    assert outer.duration_s == pytest.approx(0.003, abs=1e-6)
    assert scan.t0 - 1.0 < outer.t0 < scan.t0 + 1.0   # on the tracer's clock


def test_clock_offset_matches_the_stretch_of_a_longer_session():
    """The profile saw only the last two of three spans of a name: the
    offset is still found, and is the median over matched spans."""
    from repro.telemetry import profile

    clock = iter([1.0, 1.5, 2.0, 2.5, 3.0, 3.5, 4.0, 4.5])
    tr = SpanTracer(clock=lambda: next(clock))
    for name in ("mset.sprt", "mset.sprt", "mset.estimate", "mset.sprt"):
        with tr.span(name):
            pass
    off = 7e9
    events = [("mset.sprt", int(2.0e9 + off), int(2.5e9 + off)),
              ("mset.estimate", int(3.0e9 + off) + 40, int(3.5e9 + off)),
              ("mset.sprt", int(4.0e9 + off), int(4.5e9 + off))]
    assert profile.clock_offset_ns(tr, events) == pytest.approx(off)
    assert profile.clock_offset_ns(tr, [("other", 0, 1)]) is None
    placed = profile.placed(tr, off)
    assert placed[1] == ("mset.sprt", pytest.approx(2.0e9 + off),
                         pytest.approx(2.5e9 + off))


def test_sprt_lowers_once_inside_its_span_on_a_cpu_profile(tmp_path):
    """On a profile taken here, three calls at a shape fresh in the process
    place exactly one ``jit.lower`` under ``mset.sprt``, and it lies inside
    the ``mset.sprt`` annotation of the first call, to 50 us."""
    jax = pytest.importorskip("jax")
    import glob

    import jax.numpy as jnp

    from repro.mset import sprt
    from repro.telemetry import profile

    r = jax.random.normal(jax.random.PRNGKey(3), (72, 8))
    sigma = jnp.ones(8)
    with telemetry.session() as tel:
        jax.profiler.start_trace(str(tmp_path))
        for _ in range(3):
            sprt(r, sigma)
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    events = profile.host_events(path, ("mset.",))
    calls = [(s, e) for n, s, e in events if n == "mset.sprt"]
    assert len(calls) == 3
    offset = profile.clock_offset_ns(tel.tracer, events)
    lowers = [(s, e) for p, s, e in profile.placed(tel.tracer, offset)
              if p.startswith("mset.sprt/") and p.endswith("/jit.lower")]
    assert len(lowers) == 1          # one compiled program, lowered once
    slack = 50_000                   # ns
    (s, e), (a, b) = lowers[0], calls[0]
    assert a - slack <= s and e <= b + slack


def test_import_mset_imports_nothing_of_the_fleet():
    import os
    import subprocess
    import sys

    code = ("import sys, repro.mset; "
            "print(sorted(m for m in sys.modules if m.startswith('repro.fleet')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "[]"
