"""Portfolio-robust tuning (`repro.fleet.tuning` portfolio axis): robust
reduction invariants, numpy==jax agreement on the robust score, single-trace
identity with the pre-portfolio path, racing/sims accounting on portfolios,
candidate tiling, the persistent compile cache, and SLO-column racing."""
import json
import os

import numpy as np
import pytest

from repro import compile_cache
from repro.core import CellResult, RooflineTerms, get_shape
from repro.fleet import (FleetConfig, Objective, OracleGrid, PIPolicy,
                         PoolConfig, StaticPolicy, TuningBudget,
                         TuningScenario, TuningReport, ParamSpace, Integer,
                         evaluate_candidates, exhaustive, flash_crowd_trace,
                         poisson_trace, race, ramp_trace, robust_m,
                         robust_weights, service_model_from_cell, telemetry,
                         tune)
from repro.fleet import jaxsim
from repro.fleet.tuning.evaluate import _reduce_portfolio
from repro.fleet.tuning.racing import race_column

needs_jax = pytest.mark.skipif(not jaxsim.available(),
                               reason="jax not installed")


def _cell(shape="v5e-4", t_comp=0.4, t_mem=0.1, t_coll=0.05, batch=64):
    return CellResult(params={"batch": batch, "chips": get_shape(shape).chips},
                      shape_name=shape,
                      terms=RooflineTerms(t_comp, t_mem, t_coll),
                      analysis={"peak_memory_per_device": 1e9})


def _service(**kw):
    return service_model_from_cell(_cell(**kw),
                                   units_per_step=kw.get("batch", 64))


def _fleet(svc, initial=8, cold_start_s=30.0, **kw):
    return FleetConfig((PoolConfig(service=svc, cold_start_s=cold_start_s,
                                   initial_replicas=initial, **kw),))


def _traces(svc, duration=400.0, n_seeds=4):
    """Three demand futures sharing dt/bins/seeds: steady, flash crowd,
    ramp-down — distinct enough that per-trace winners differ."""
    mt = svc.max_throughput
    return [poisson_trace(3.0 * mt, duration, dt_s=5.0, n_seeds=n_seeds,
                          seed=0),
            flash_crowd_trace(2.0 * mt, duration, dt_s=5.0, n_seeds=n_seeds,
                              seed=1, peak_mult=4.0),
            ramp_trace(4.0 * mt, 1.0 * mt, duration, dt_s=5.0,
                       n_seeds=n_seeds, seed=2)]


def _portfolio_scenario(svc=None, robust="worst_case", backend="auto",
                        n_traces=3, **kw):
    svc = svc or _service()
    return TuningScenario(
        name="portfolio", workload=_traces(svc, **kw)[:n_traces],
        fleet=_fleet(svc), policy_cls=StaticPolicy,
        context={"slo_s": 2.0}, robust=robust, backend=backend)


SPACE = ParamSpace((Integer("n_replicas", 1, 16),))


# -------------------------- robust reduction --------------------------------

def test_robust_m_specs():
    assert robust_m("worst_case", 5) == 1
    assert robust_m("mean", 5) == 5
    assert robust_m("cvar(0.4)", 5) == 2
    assert robust_m("cvar(1.0)", 5) == 5
    assert robust_m("cvar(1e-6)", 5) == 1
    for bad in ("median", "cvar(0)", "cvar(1.5)", "cvar(-0.2)", "worstcase"):
        with pytest.raises(ValueError):
            robust_m(bad, 5)


def test_robust_weights_invariants_hypothesis():
    """For any per-trace score matrix: weights are a per-seed probability
    simplex supported on the m worst traces; worst_case reduces to the
    column max; cvar interpolates monotonically between worst_case and mean
    and is bounded by both."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st
    from hypothesis.extra import numpy as hnp

    @settings(max_examples=40, deadline=None)
    @given(hnp.arrays(np.float64, hnp.array_shapes(min_dims=2, max_dims=2,
                                                   min_side=1, max_side=6),
                      elements=st.floats(-1e6, 1e6)),
           st.floats(1e-3, 1.0))
    def check(scores, alpha):
        K = scores.shape[0]
        for spec in ("worst_case", "mean", f"cvar({alpha})"):
            w = robust_weights(scores, spec)
            assert w.shape == scores.shape
            np.testing.assert_allclose(w.sum(axis=0), 1.0)
            assert ((w == 0) | np.isclose(w, 1.0 / robust_m(spec, K))).all()
        red = {spec: (robust_weights(scores, spec) * scores).sum(axis=0)
               for spec in ("worst_case", "mean", f"cvar({alpha})",
                            "cvar(1.0)")}
        np.testing.assert_allclose(red["worst_case"], scores.max(axis=0))
        np.testing.assert_allclose(red["mean"], scores.mean(axis=0))
        np.testing.assert_allclose(red["cvar(1.0)"], red["mean"])
        cv = red[f"cvar({alpha})"]
        assert (cv <= red["worst_case"] + 1e-9).all()
        assert (cv >= red["mean"] - 1e-6 * np.abs(red["mean"]) - 1e-9).all()

    check()


def test_cvar_monotone_in_alpha():
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(6, 5)) * 100
    alphas = np.linspace(0.05, 1.0, 12)
    reds = [(robust_weights(scores, f"cvar({a})") * scores).sum(axis=0)
            for a in alphas]
    for hi, lo in zip(reds, reds[1:]):   # averaging over more traces can
        assert (lo <= hi + 1e-9).all()   # only soften the tail


def test_reduced_score_permutation_invariant():
    """The robust *score* never depends on trace order (stable tie-break
    changes which trace's cost rides along, never the score)."""
    rng = np.random.default_rng(3)

    def ev(seed):
        r = np.random.default_rng(seed)
        return _fake_eval(r.uniform(1, 9, 5), r.uniform(0.8, 1.0, 5))

    per = [ev(i) for i in range(4)]
    for spec in ("worst_case", "mean", "cvar(0.5)"):
        base = _reduce_portfolio(per, spec).score
        for _ in range(5):
            perm = rng.permutation(4)
            got = _reduce_portfolio([per[i] for i in perm], spec).score
            if spec == "worst_case":     # m=1: the worst row verbatim
                np.testing.assert_array_equal(got, base)
            else:                        # m>1 sums m rows: order-of-addition
                np.testing.assert_allclose(got, base, rtol=1e-12)


def _fake_eval(cost, att, objective=Objective()):
    from repro.fleet.tuning.evaluate import CandidateEval
    cost, att = np.asarray(cost, float), np.asarray(att, float)
    return CandidateEval(params={"n_replicas": 3}, cost_usd_hr=cost,
                         attainment=att, drop_rate=np.zeros_like(cost),
                         score=np.asarray(objective.score(cost, att)),
                         sojourns=[])


def test_worst_case_reduction_picks_worst_trace_rows():
    a = _fake_eval([1.0, 9.0], [1.0, 1.0])
    b = _fake_eval([5.0, 2.0], [1.0, 1.0])
    red = _reduce_portfolio([a, b], "worst_case")
    np.testing.assert_array_equal(red.score, [5.0, 9.0])
    np.testing.assert_array_equal(red.cost_usd_hr, [5.0, 9.0])
    assert red.worst_trace_score() == max(a.mean_score(), b.mean_score())
    assert red.per_trace[0] is a and red.per_trace[1] is b


# ----------------------- scenario construction ------------------------------

def test_portfolio_member_validation():
    svc = _service()
    t1 = poisson_trace(100.0, 400.0, dt_s=5.0, n_seeds=4, seed=0)
    bad_seeds = poisson_trace(100.0, 400.0, dt_s=5.0, n_seeds=8, seed=1)
    bad_dt = poisson_trace(100.0, 400.0, dt_s=10.0, n_seeds=4, seed=1)
    kw = dict(name="p", fleet=_fleet(svc), policy_cls=StaticPolicy)
    with pytest.raises(ValueError, match="seeds"):
        TuningScenario(workload=[t1, bad_seeds], context={"slo_s": 2.0}, **kw)
    with pytest.raises(ValueError, match="match the primary"):
        TuningScenario(workload=[t1, bad_dt], context={"slo_s": 2.0}, **kw)
    with pytest.raises(ValueError, match="slo_s"):
        TuningScenario(workload=[t1], context={}, **kw)
    with pytest.raises(ValueError, match="empty"):
        TuningScenario(workload=[], context={"slo_s": 2.0}, **kw)
    with pytest.raises(ValueError, match="robust"):
        TuningScenario(workload=[t1], context={"slo_s": 2.0},
                       robust="median", **kw)


def test_single_trace_portfolio_identical_to_plain():
    """A one-member portfolio is byte-identical to passing the trace
    directly — same winner, same per-seed evidence, same report numbers."""
    svc = _service()
    tr = _traces(svc)[0]
    kw = dict(fleet=_fleet(svc), policy_cls=StaticPolicy,
              context={"slo_s": 2.0})
    plain = tune(TuningScenario(name="s", workload=tr, **kw), SPACE, seed=0)
    port = tune(TuningScenario(name="s", workload=[tr], **kw), SPACE, seed=0)
    assert plain.winner.params == port.winner.params
    np.testing.assert_array_equal(plain.winner.score, port.winner.score)
    np.testing.assert_array_equal(plain.winner.cost_usd_hr,
                                  port.winner.cost_usd_hr)
    assert plain.sims_used == port.sims_used
    assert plain.full_budget == port.full_budget
    assert port.n_traces == 1 and port.robust is None
    assert port.winner.per_trace is None


# ------------------------- backend agreement --------------------------------

@needs_jax
@pytest.mark.parametrize("robust", ["worst_case", "cvar(0.67)", "mean"])
def test_numpy_jax_robust_score_exact(robust):
    """The compiled portfolio dispatch and the numpy per-member loop agree
    on the robust score to the last bit (same host-side reduction on
    bit-identical dynamics), hence on the winner."""
    svc = _service()
    cands = [{"n_replicas": n} for n in (2, 5, 9, 14)]
    evs = {}
    for backend in ("numpy", "jax"):
        sc = _portfolio_scenario(svc, robust=robust, backend=backend)
        evs[backend] = evaluate_candidates(sc, cands, Objective())
    for a, b in zip(evs["numpy"], evs["jax"]):
        np.testing.assert_array_equal(a.score, b.score)
        np.testing.assert_array_equal(a.attainment, b.attainment)
        for ta, tb in zip(a.per_trace, b.per_trace):
            np.testing.assert_array_equal(ta.score, tb.score)
    pick = {k: min(v, key=lambda e: e.mean_score()).params
            for k, v in evs.items()}
    assert pick["numpy"] == pick["jax"]


# --------------------------- racing on portfolios ----------------------------

def test_portfolio_known_optimum_never_culled():
    """Racing a portfolio must return the exhaustive robust winner (the
    paired SPRT operates on the reduced score, so the known optimum under
    the robust objective survives every cull)."""
    sc = _portfolio_scenario()
    cands = SPACE.grid(16)
    ex = exhaustive(sc, cands, Objective())
    for init_seeds in (1, 2):
        rr = race(sc, cands, Objective(), init_seeds=init_seeds)
        assert rr.winner.params == ex.winner.params
        assert rr.sims_used <= ex.sims_used


def test_portfolio_sims_accounting():
    """sims_used / full_budget count candidate x seed x TRACE trajectories:
    one replicate of a K-trace portfolio costs K sims whichever backend
    dispatches it."""
    sc = _portfolio_scenario(n_traces=3)
    cands = SPACE.sample_lhs(6, seed=1)
    ex = exhaustive(sc, cands, Objective())
    assert ex.sims_used == ex.full_budget == 6 * sc.n_seeds * 3
    rr = race(sc, cands, Objective())
    assert rr.full_budget == 6 * sc.n_seeds * 3
    assert rr.sims_used % 3 == 0
    assert rr.sims_used < ex.sims_used
    rep = tune(sc, SPACE, seed=0)
    assert rep.n_traces == 3 and rep.robust == "worst_case"
    assert rep.full_budget == len(SPACE.sample_lhs(24, seed=0)) \
        * sc.n_seeds * 3
    assert "portfolio: 3 traces" in rep.summary()


def test_portfolio_report_roundtrip():
    rep = tune(_portfolio_scenario(), SPACE,
               budget=TuningBudget(n_candidates=5), seed=2)
    back = TuningReport.from_json(rep.to_json())
    assert back.n_traces == rep.n_traces and back.robust == rep.robust
    assert len(back.winner.per_trace) == 3
    np.testing.assert_array_equal(back.winner.score, rep.winner.score)
    np.testing.assert_array_equal(back.winner.per_trace[1].score,
                                  rep.winner.per_trace[1].score)
    assert back.winner.worst_trace_score() == rep.winner.worst_trace_score()


# ----------------------------- candidate tiling ------------------------------

@needs_jax
def test_tiled_dispatch_bit_exact_and_warm_after_first():
    """A slate wider than the tile streams through fixed-shape chunks: every
    tile after the first reuses the compiled program (warm), the padded tail
    included, and the results are bit-identical to one wide dispatch."""
    svc = _service()
    tr = poisson_trace(3.0 * svc.max_throughput, 300.0, dt_s=5.0, n_seeds=3,
                       seed=0)
    kw = dict(name="t", workload=tr, fleet=_fleet(svc),
              policy_cls=StaticPolicy, context={"slo_s": 2.0}, backend="jax")
    cands = [{"n_replicas": 1 + (i % 16)} for i in range(40)]
    jaxsim.clear_compiled()
    with telemetry.session() as tel:
        tiled = evaluate_candidates(TuningScenario(tile=16, **kw), cands,
                                    Objective())
    spans = [s for s in _walk_spans(tel.tracer.roots)
             if s.name == "jaxsim.dispatch"]
    assert len(spans) == 3                       # ceil(40 / 16) tiles
    assert [s.attrs["kind"] for s in spans] == ["cold", "warm", "warm"]
    assert all(s.attrs["padded"] == 16 for s in spans)
    assert [s.attrs["tile"] for s in spans] == [0, 1, 2]
    assert spans[-1].attrs["candidates"] == 8    # tail padded to the tile
    flat = evaluate_candidates(TuningScenario(tile=None, **kw), cands,
                               Objective())
    for a, b in zip(tiled, flat):
        np.testing.assert_array_equal(a.score, b.score)


def _walk_spans(spans):
    for s in spans:
        yield s
        yield from _walk_spans(s.children)


@needs_jax
def test_telemetry_off_is_bit_exact():
    sc = _portfolio_scenario(backend="jax")
    cands = [{"n_replicas": 4}, {"n_replicas": 11}]
    off = evaluate_candidates(sc, cands, Objective())
    with telemetry.session():
        on = evaluate_candidates(sc, cands, Objective())
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.score, b.score)


# ------------------------ persistent compile cache ---------------------------

@needs_jax
def test_persistent_compile_cache_disk_hits(tmp_path):
    """With an on-disk compile cache, re-tracing after an in-memory flush
    loads the compiled program from disk (counter-verified hits) and the
    results stay bit-identical."""
    cache = tmp_path / "jaxcache"
    compile_cache.use(str(cache))
    try:
        svc = _service(t_comp=0.37)  # fresh shape -> fresh compiled core
        tr = poisson_trace(3.0 * svc.max_throughput, 300.0, dt_s=5.0,
                           n_seeds=3, seed=0)
        sc = TuningScenario(name="c", workload=tr, fleet=_fleet(svc),
                            policy_cls=StaticPolicy, context={"slo_s": 2.0},
                            backend="jax")
        cands = [{"n_replicas": 5}]
        before = jaxsim.persistent_cache_stats()
        cold = evaluate_candidates(sc, cands, Objective())
        mid = jaxsim.persistent_cache_stats()
        assert mid["misses"] > before["misses"]  # compiled + written to disk
        assert any(cache.rglob("*"))
        evicted = jaxsim.clear_compiled()        # keep cores alive: a fresh
        assert evicted                           # core must not reuse an id()
        with telemetry.session() as tel:
            warm = evaluate_candidates(sc, cands, Objective())
        after = jaxsim.persistent_cache_stats()
        assert after["hits"] > mid["hits"]
        snap = tel.metrics.snapshot()["counter"]
        assert snap["jax_compile_events_total"]["phase=cache_hit"] >= 1
        for a, b in zip(cold, warm):
            np.testing.assert_array_equal(a.score, b.score)
    finally:
        # cache config is process-global: later tests in this pytest process
        # must not keep serializing every jit through the reaped tmp dir
        compile_cache.use(None)
        jaxsim.clear_compiled()


# ----------------------------- SLO-column racing -----------------------------

@needs_jax
def test_race_column_matches_per_tier_race():
    """One shared-dispatch column race returns, per SLO tier, exactly the
    winner/evidence/spend a standalone per-tier race produces, while the
    physical trajectory count covers the column once, not once per tier."""
    svc = _service()
    tr = poisson_trace(3.0 * svc.max_throughput, 400.0, dt_s=5.0, n_seeds=4,
                       seed=0)
    slos = (1.0, 2.5, 6.0)
    cands = PIPolicy.param_space().sample_lhs(6, seed=3)

    def scen(slo):
        from repro.fleet.workload import Workload
        return TuningScenario(name=f"tier{slo}",
                              workload=Workload.from_trace(tr, slo),
                              fleet=_fleet(svc, max_replicas=24),
                              policy_cls=PIPolicy, context={"slo_s": slo},
                              backend="jax")

    got = race_column(scen(slos[0]), cands, Objective(), slos)
    assert got is not None
    results, sims_shared = got
    per_tier_total = 0
    for slo, rr in zip(slos, results):
        solo = race(scen(slo), cands, Objective())
        assert rr.winner.params == solo.winner.params
        np.testing.assert_array_equal(rr.winner.score, solo.winner.score)
        assert rr.sims_used == solo.sims_used
        assert rr.full_budget == solo.full_budget
        assert rr.culled_at_round == solo.culled_at_round
        per_tier_total += rr.sims_used
    assert sims_shared <= per_tier_total
    assert sims_shared >= max(r.sims_used for r in results)


@needs_jax
def test_race_column_declines_multiclass():
    """Multi-class tiers have SLO-dependent dynamics (EDF keys, hetero
    critical demand); the column path must refuse rather than share."""
    from repro.fleet.scenarios import tiered_sla_workload
    svc = _service()
    wl = tiered_sla_workload(3.0 * svc.max_throughput, 400.0, dt_s=5.0,
                             n_seeds=2)
    sc = TuningScenario(name="m", workload=wl, fleet=_fleet(svc),
                        policy_cls=PIPolicy, context={"slo_s": 1.0},
                        backend="jax")
    assert race_column(sc, PIPolicy.param_space().sample_lhs(3, seed=0),
                       Objective(), (1.0, 2.0)) is None


@needs_jax
def test_oracle_column_batch_matches_per_cell():
    """build_oracle's shared-column path: identical winners, scores and
    frontiers to the per-cell sweep, at a fraction of the physical sims."""
    from repro.fleet.oracle import build_oracle
    svc = _service()
    fleet = _fleet(svc, max_replicas=24)
    mt = svc.max_throughput
    grid = OracleGrid(mean_rates=(3.0 * mt,), burstiness=(1.4,),
                      slos=(1.0, 3.0), duration_s=400.0, dt_s=5.0,
                      n_seeds=2, seed=3)
    kw = dict(objective=Objective(min_attainment=0.9),
              budget=TuningBudget(n_candidates=4, init_seeds=1),
              backend="jax")
    t_col = build_oracle(grid, fleet, PIPolicy, PIPolicy.param_space(), **kw)
    t_cell = build_oracle(grid, fleet, PIPolicy, PIPolicy.param_space(),
                          column_batch=False, **kw)
    for k in t_cell.cells:
        assert t_col.cells[k].winner == t_cell.cells[k].winner
        assert t_col.cells[k].score == t_cell.cells[k].score
        assert t_col.cells[k].frontier == t_cell.cells[k].frontier
    assert t_col.build_info["sims_used"] < t_cell.build_info["sims_used"]


# --------------------------------- CI gate ----------------------------------

def _load_check_bench():
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "check_bench.py")
    spec = importlib.util.spec_from_file_location("check_bench_portfolio",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _green_portfolio():
    tiles = [{"kind": "warm", "tile": i, "padded": 128, "candidates": 128}
             for i in range(4)]
    cold = [dict(t) for t in tiles]
    cold[0]["kind"] = "cold"
    return {
        "benchmark": "portfolio_tuning",
        "headline": {
            "n_candidates": 512, "n_traces": 4, "n_seeds": 4,
            "tile": 128, "n_tiles": 4, "jax_warm_s": 4.7, "speedup": 22.9,
            "cold_round_dispatches": cold, "warm_round_dispatches": tiles,
            "subset_max_score_delta": 0.0,
        },
        "robustness": {
            "portfolio_winner": {"worst_trace_score": 1067.0,
                                 "worst_trace_attainment": 0.89},
            "single_trace_winners": [
                {"tuned_on": "flash", "worst_trace_score": 1337.0},
                {"tuned_on": "ramp", "worst_trace_score": 4807.0},
            ],
            "portfolio_dominates": True,
        },
        "agreement": {"max_robust_score_delta": 0.0, "same_winner": True},
        "compile_cache": {
            "cold_build": {"cold_dispatch_s": 1.3, "disk_misses": 2,
                           "disk_hits": 0},
            "warm_build": {"cold_dispatch_s": 0.5, "disk_misses": 0,
                           "disk_hits": 2},
            "max_score_delta": 0.0,
        },
    }


def test_compare_portfolio_green():
    cb = _load_check_bench()
    assert cb.compare_portfolio(_green_portfolio(), _green_portfolio(),
                                0.02, 0.08, 2.0) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d["headline"].__setitem__("speedup", 1.2), "bar 5.0x"),
    (lambda d: d["headline"]["warm_round_dispatches"][1].__setitem__(
        "kind", "cold"), "warm dispatch per tile"),
    (lambda d: d["headline"].__setitem__(
        "warm_round_dispatches",
        d["headline"]["warm_round_dispatches"] * 4), "warm dispatch per tile"),
    (lambda d: d["headline"]["cold_round_dispatches"][0].__setitem__(
        "kind", "warm"), "compile once"),
    (lambda d: d["headline"].__setitem__("subset_max_score_delta", 1e-9),
     "subset"),
    (lambda d: d["robustness"].__setitem__("portfolio_dominates", False),
     "robustness headline"),
    (lambda d: d["robustness"]["portfolio_winner"].__setitem__(
        "worst_trace_score", 5000.0), "rose"),
    (lambda d: d["agreement"].__setitem__("max_robust_score_delta", 1e-12),
     "disagree"),
    (lambda d: d["agreement"].__setitem__("same_winner", False), "winner"),
    (lambda d: d["compile_cache"]["warm_build"].__setitem__("disk_hits", 0),
     "disk hits"),
    (lambda d: d["compile_cache"]["cold_build"].__setitem__("disk_misses", 0),
     "not wired"),
    (lambda d: d["compile_cache"]["warm_build"].__setitem__(
        "cold_dispatch_s", 2.0), "not faster"),
    (lambda d: d["compile_cache"].__setitem__("max_score_delta", 1e-9),
     "deserialized"),
    (lambda d: d.__setitem__("error", "no jax"), "did not run"),
])
def test_compare_portfolio_red(mutate, needle):
    cb = _load_check_bench()
    fresh = _green_portfolio()
    mutate(fresh)
    problems = cb.compare_portfolio(fresh, _green_portfolio(), 0.02, 0.08,
                                    2.0)
    assert problems, f"expected a problem mentioning {needle!r}"
    assert any(needle.lower() in p.lower() for p in problems), problems


def test_compare_tuner_joint_optimum_red():
    """compare_tuner flags a missing/broken joint_optimum section."""
    cb = _load_check_bench()
    base = {"headline": {}}
    green = {
        "headline": {"tuned": {"usd_per_hour": 25.0,
                               "worst_class_attainment": 1.0},
                     "default": {"usd_per_hour": 29.0,
                                 "worst_class_attainment": 1.0},
                     "tuned_dominates_default": True},
        "surface_r2": 0.85,
        "budget": {"frac": 0.2},
        "race_vs_exhaustive": {"same_winner": True, "race_frac": 0.27},
        "joint_optimum": {
            "greedy": {"params": {"discipline": "fifo", "n_replicas": 11},
                       "score": 52.8},
            "joint": {"params": {"discipline": "priority", "n_replicas": 8},
                      "score": 38.4},
        },
    }
    assert cb.compare_tuner(dict(green), base, 0.02, 0.08, 2.0) == []
    broken = json.loads(json.dumps(green))
    del broken["joint_optimum"]
    assert any("joint_optimum" in p
               for p in cb.compare_tuner(broken, base, 0.02, 0.08, 2.0))
    tied = json.loads(json.dumps(green))
    tied["joint_optimum"]["joint"] = dict(
        tied["joint_optimum"]["greedy"])
    problems = cb.compare_tuner(tied, base, 0.02, 0.08, 2.0)
    assert any("greedy" in p for p in problems)
