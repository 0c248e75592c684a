"""Portfolio-robust tuning benchmark: 4 demand futures x >= 512 candidates
in one tiled compiled dispatch chain, plus the robustness headline.

Four traces — flash crowd, diurnal, an Azure-replay window, an adversarial
cooling ramp — ride ONE jitted candidate x (seed x trace) lattice per
candidate tile (`TuningScenario(workload=[...], tile=...)`): no per-trace
Python loop, every tile after the first a warm dispatch. The headlines this
benchmark pins (and ``tools/check_bench.py`` gates against
``benchmarks/baselines/portfolio.json``):

* a 4-trace x 512-candidate evaluation round executes one dispatch per
  candidate tile (span-verified: 1 cold + warm repeats after a flush, all
  warm once compiled) and beats the per-trace sequential numpy path by
  >= 5x on per-trajectory throughput;
* numpy and jax agree on the robust score to the last bit (delta 0) and on
  the round winner;
* robustness dominance: the portfolio winner's worst-trace score is at
  least as good as EVERY single-trace winner's worst-trace score — tuning
  on one trace overfits, the portfolio does not;
* a second build with a warm persistent compile cache spends measurably
  less wall-clock compiling than the cold build (disk-hit counter-verified,
  with a timing-noise grace floor).

Results land in ``BENCH_portfolio.json`` (CI artifact).

    PYTHONPATH=src python benchmarks/portfolio.py [--full] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import compile_cache
from repro.core.recommender import recommend
from repro.fleet import (FleetConfig, Objective, PredictivePolicy, Trace,
                         TuningBudget, diurnal_trace, evaluate_candidates,
                         flash_crowd_trace, load_trace_csv, mset_scenario,
                         ramp_trace, resample_trace, telemetry, tune,
                         tuning_scenario)
from repro.fleet import jaxsim

QUOTA = 16
COLD_START_S = 60.0
SEED = 0
DT_S = 5.0
TILE = 128
DATA_CSV = os.path.join(os.path.dirname(__file__), "data",
                        "azure_functions_day.csv")


def build_portfolio(svc, duration: float, n_seeds: int):
    """The pinned 4-member demand portfolio, every member sharing
    (dt, bins, seeds): flash crowd (burst), diurnal (one full cycle),
    the busiest same-length window of the Azure functions replay, and an
    adversarial cooling ramp (starts hot — punishes slow scale-up the
    other members never probe)."""
    mt = svc.max_throughput
    flash = flash_crowd_trace(3.5 * mt, duration, dt_s=DT_S, peak_mult=4.0,
                              burst_width_s=duration / 30,
                              n_seeds=n_seeds, seed=SEED + 2)
    diurnal = diurnal_trace(3.5 * mt, duration, dt_s=DT_S, amplitude=0.7,
                            period_s=duration, n_seeds=n_seeds, seed=SEED + 3)
    day = load_trace_csv(DATA_CSV, rate_col=1, dt_s=60.0,
                         mean_rate_per_s=3.5 * mt, n_seeds=n_seeds,
                         seed=SEED + 4)
    k = int(round(duration / 60.0))          # busiest duration-long window
    means = np.convolve(day.rate, np.ones(k) / k, mode="valid")
    b0 = int(np.argmax(means))
    window = Trace("azure-window", 60.0, day.rate[b0:b0 + k],
                   day.arrivals[:, b0:b0 + k])
    azure = resample_trace(window, DT_S, seed=SEED + 4)
    ramp = ramp_trace(6.0 * mt, 1.0 * mt, duration, dt_s=DT_S,
                      n_seeds=n_seeds, seed=SEED + 5)
    return [flash, diurnal, azure, ramp]


def build_scenario(full: bool = False, backend: str = "auto", *,
                   robust: str = "worst_case", tile: int = TILE,
                   workload=None):
    scenario = mset_scenario(n_signals=1024, n_memvec=4096, fleet=8,
                             slo_s=1.0)
    svc = scenario.service_for(scenario.cheapest_shape())
    duration = 2400.0 if full else 1200.0
    n_seeds = 6 if full else 4
    if workload is None:
        workload = build_portfolio(svc, duration, n_seeds)
    shape = recommend(scenario.rows_at(), scenario.constraint()).shape.name
    fleet = FleetConfig((scenario.pool_for(shape, cold_start_s=COLD_START_S,
                                           max_replicas=QUOTA),))
    return tuning_scenario(scenario, workload, PredictivePolicy, fleet=fleet,
                           cold_start_s=COLD_START_S, backend=backend,
                           robust=robust, tile=tile), svc


def _objective():
    return Objective(min_attainment=0.99, penalty_usd_per_hour=1e4)


def _dispatch_spans(tel):
    def walk(spans):
        for s in spans:
            if s.name == "jaxsim.dispatch":
                yield s
            yield from walk(s.children)
    return [{"kind": s.attrs.get("kind"), "tile": s.attrs.get("tile"),
             "padded": s.attrs.get("padded"),
             "candidates": s.attrs.get("candidates")}
            for s in walk(tel.tracer.roots)]


def run_headline(ts, objective, n_candidates: int, numpy_subset: int):
    """One full-replicate evaluation round over the whole slate — exactly
    what a racing round dispatches — timed compiled-tiled vs the per-trace
    sequential numpy reference on a subset, compared on per-trajectory
    throughput (each of the ``n x seeds x traces`` trajectories is the same
    amount of physics on either path)."""
    space = PredictivePolicy.param_space()
    cands = space.sample_lhs(n_candidates, seed=SEED)
    K, S = ts.n_traces, ts.n_seeds

    held = jaxsim.clear_compiled()           # hold refs: id()-reuse hazard
    with telemetry.session() as tel:
        evaluate_candidates(ts, cands, objective)
    cold_round = _dispatch_spans(tel)
    with telemetry.session() as tel:
        t0 = time.perf_counter()
        evals = evaluate_candidates(ts, cands, objective)
        jax_warm_s = time.perf_counter() - t0
    warm_round = _dispatch_spans(tel)
    del held

    ts_np, _ = build_scenario(backend="numpy",
                              workload=list(ts.portfolio))
    t0 = time.perf_counter()
    np_evals = evaluate_candidates(ts_np, cands[:numpy_subset], objective)
    numpy_s = time.perf_counter() - t0

    jax_per_sim_us = jax_warm_s / (len(cands) * K * S) * 1e6
    numpy_per_sim_us = numpy_s / (numpy_subset * K * S) * 1e6
    winner = min(evals, key=lambda e: e.mean_score())
    sub_delta = float(max(
        np.abs(a.score - b.score).max()
        for a, b in zip(np_evals, evals[:numpy_subset])))
    n_tiles = int(np.ceil(len(cands) / TILE))
    return evals, {
        "n_candidates": len(cands),
        "n_traces": K, "n_seeds": S, "tile": TILE, "n_tiles": n_tiles,
        "n_bins": ts.workload.n_bins,
        "jax_warm_s": jax_warm_s,
        "jax_per_sim_us": jax_per_sim_us,
        "numpy_subset_candidates": numpy_subset,
        "numpy_s": numpy_s,
        "numpy_per_sim_us": numpy_per_sim_us,
        "speedup": numpy_per_sim_us / max(jax_per_sim_us, 1e-12),
        "cold_round_dispatches": cold_round,
        "warm_round_dispatches": warm_round,
        "subset_max_score_delta": sub_delta,
        "winner": dict(winner.params),
    }


def run_robustness(ts, objective, budget):
    """The overfit table: tune on each trace alone, tune on the portfolio,
    then score every winner on the full portfolio. A single-trace winner's
    worst trace is its blind spot; the portfolio winner must have none
    worse."""
    space = PredictivePolicy.param_space()
    port_report = tune(ts, space, objective, budget, seed=SEED)

    rows, winners = [], []
    for k, member in enumerate(ts.portfolio):
        ts_k, _ = build_scenario(workload=[member])
        rep = tune(ts_k, space, objective, budget, seed=SEED)
        winners.append((member.name, dict(rep.winner.params)))
    # score each single-trace winner ON the portfolio (full replicates,
    # same paired draws as the portfolio tune)
    evals = evaluate_candidates(ts, [w for _, w in winners]
                                + [dict(port_report.winner.params)],
                                objective)
    for (name, params), ev in zip(winners, evals[:-1]):
        rows.append({
            "tuned_on": name, "params": params,
            "own_trace_score": min(t.mean_score() for t in ev.per_trace),
            "worst_trace_score": ev.worst_trace_score(),
            "worst_trace_attainment": ev.worst_trace_attainment(),
        })
    pev = evals[-1]
    port = {
        "robust": ts.robust, "params": dict(pev.params),
        "worst_trace_score": pev.worst_trace_score(),
        "worst_trace_attainment": pev.worst_trace_attainment(),
        "per_trace_scores": {m.name: t.mean_score()
                             for m, t in zip(ts.portfolio, pev.per_trace)},
        "sims_used": port_report.sims_used,
        "full_budget": port_report.full_budget,
    }
    dominance = all(port["worst_trace_score"] <= r["worst_trace_score"] + 1e-9
                    for r in rows)
    return {"portfolio_winner": port, "single_trace_winners": rows,
            "portfolio_dominates": bool(dominance)}


def run_agreement(ts, objective):
    """numpy and jax must agree on the robust score bit-for-bit."""
    space = PredictivePolicy.param_space()
    cands = space.sample_lhs(8, seed=SEED + 9)
    ts_np, _ = build_scenario(backend="numpy",
                              workload=list(ts.portfolio))
    ej = evaluate_candidates(ts, cands, objective)
    en = evaluate_candidates(ts_np, cands, objective)
    delta = float(max(np.abs(a.score - b.score).max()
                      for a, b in zip(en, ej)))
    wj = min(ej, key=lambda e: e.mean_score()).params
    wn = min(en, key=lambda e: e.mean_score()).params
    return {"n_candidates": len(cands),
            "max_robust_score_delta": delta,
            "same_winner": wj == wn,
            "jax_winner": dict(wj), "numpy_winner": dict(wn)}


def run_compile_cache(ts, objective, cache_dir: str):
    """Cold build vs disk-warm rebuild: flush the in-memory jit caches, pay
    XLA compilation once into the persistent cache, flush again, and verify
    the rebuild deserializes from disk (hit counters) with measurably less
    cold-dispatch wall-clock. The persistent cache must point at an empty
    ``cache_dir``."""
    cands = PredictivePolicy.param_space().sample_lhs(12, seed=SEED + 7)

    def cold_build():
        held = jaxsim.clear_compiled()
        with telemetry.session() as tel:
            t0 = time.perf_counter()
            evals = evaluate_candidates(ts, cands, objective, s1=2)
            wall = time.perf_counter() - t0
        del held
        snap = tel.metrics.snapshot()["counter"]
        cold_s = snap.get("jaxsim_dispatch_seconds_total",
                          {}).get("kind=cold", 0.0)
        return evals, wall, cold_s

    before = jaxsim.persistent_cache_stats()
    e1, wall1, cold1 = cold_build()
    mid = jaxsim.persistent_cache_stats()
    e2, wall2, cold2 = cold_build()
    after = jaxsim.persistent_cache_stats()
    delta = float(max(np.abs(a.score - b.score).max()
                      for a, b in zip(e1, e2)))
    return {
        "cache_dir_entries": sum(len(f) for _, _, f in os.walk(cache_dir)),
        "cold_build": {"wall_s": wall1, "cold_dispatch_s": cold1,
                       "disk_misses": mid["misses"] - before["misses"],
                       "disk_hits": mid["hits"] - before["hits"]},
        "warm_build": {"wall_s": wall2, "cold_dispatch_s": cold2,
                       "disk_misses": after["misses"] - mid["misses"],
                       "disk_hits": after["hits"] - mid["hits"]},
        "compile_seconds_saved": cold1 - cold2,
        "max_score_delta": delta,
    }


def run(full: bool = False):
    if not jaxsim.available():
        return {"benchmark": "portfolio_tuning", "full": full,
                "error": "jax not installed — the portfolio benchmark "
                         "measures the compiled tiled dispatch path"}
    ts, svc = build_scenario(full)
    objective = _objective()
    n_candidates = 1024 if full else 512
    budget = TuningBudget(n_candidates=32 if full else 24)

    t0 = time.perf_counter()
    _, headline = run_headline(ts, objective, n_candidates,
                               numpy_subset=64 if full else 48)
    robustness = run_robustness(ts, objective, budget)
    agreement = run_agreement(ts, objective)
    # a fixed subdirectory of the cache's home, emptied so the first build
    # is cold; the normal placement comes back afterwards. Two runs of this
    # section must not share one $JAX_COMPILATION_CACHE_DIR at the same time:
    # one's emptying would turn the other's warm rebuild cold
    cache_dir = os.path.join(compile_cache.home(), "portfolio-cold-warm")
    shutil.rmtree(cache_dir, ignore_errors=True)
    compile_cache.use(cache_dir)
    try:
        cache = run_compile_cache(ts, objective, cache_dir)
    finally:
        compile_cache.place()
    return {
        "benchmark": "portfolio_tuning",
        "full": full,
        "scenario": ts.name,
        "policy_family": "predictive",
        "portfolio": [{"trace": m.name,
                       "mean_rate_per_s": float(m.total_trace().rate.mean()),
                       "peak_rate_per_s": float(m.total_trace().rate.max())}
                      for m in ts.portfolio],
        "service_max_throughput": svc.max_throughput,
        "headline": headline,
        "robustness": robustness,
        "agreement": agreement,
        "compile_cache": cache,
        "total_wall_clock_s": time.perf_counter() - t0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_portfolio.json",
                    help="JSON results path (CI uploads this artifact)")
    args = ap.parse_args()
    compile_cache.place()
    bench = run(full=args.full)
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2)
    if "error" in bench:
        print(f"SKIPPED: {bench['error']}")
        return
    h, r, c = bench["headline"], bench["robustness"], bench["compile_cache"]
    print(f"headline: {h['n_candidates']} candidates x {h['n_traces']} "
          f"traces x {h['n_seeds']} seeds in {h['jax_warm_s']:.2f}s warm "
          f"({h['n_tiles']} tiled dispatches, "
          f"{h['jax_per_sim_us']:.0f}us/sim) — "
          f"{h['speedup']:.1f}x the sequential numpy path "
          f"({h['numpy_per_sim_us']:.0f}us/sim)")
    pw = r["portfolio_winner"]
    print(f"robustness: portfolio winner worst-trace score "
          f"${pw['worst_trace_score']:.2f} vs single-trace winners "
          + ", ".join(f"{row['tuned_on']} ${row['worst_trace_score']:.2f}"
                      for row in r["single_trace_winners"])
          + f" — dominates={r['portfolio_dominates']}")
    print(f"agreement: max robust score delta "
          f"{bench['agreement']['max_robust_score_delta']:.1e}, same winner "
          f"= {bench['agreement']['same_winner']}")
    print(f"compile cache: cold build {c['cold_build']['cold_dispatch_s']:.2f}s"
          f" compiling ({c['cold_build']['disk_misses']} disk misses), warm "
          f"rebuild {c['warm_build']['cold_dispatch_s']:.2f}s "
          f"({c['warm_build']['disk_hits']} disk hits) — saved "
          f"{c['compile_seconds_saved']:.2f}s")
    print(f"wrote {args.out} "
          f"(total wall clock {bench['total_wall_clock_s']:.1f}s)")


if __name__ == "__main__":
    main()
