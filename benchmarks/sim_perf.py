"""Simulator-backend benchmark: sequential numpy loop vs compiled JAX
candidate x seed batching, at a grid of (candidates, seeds, bins) sizes.

Each cell scores one full racing-round slate — ``evaluate_candidates`` over a
Latin-hypercube of predictive-policy configs on the flash-crowd tuning
scenario (the same build as ``tune_controller.py``). The numpy number is the
reference per-candidate loop; the JAX numbers are the one-dispatch batched
path, reported both cold (first call, includes XLA compile) and warm (the
steady state racing actually runs in — every round after the first reuses
the compiled program). The two backends are also cross-checked: per-seed
scores must agree to float tolerance and pick the same winner.

The headline ``tools/check_bench.py`` gates (``BENCH_sim.json`` vs
``benchmarks/baselines/sim.json``): on the tune_controller-sized round
(24 candidates x 12 seeds x 720 bins) the warm JAX path must beat the numpy
loop by at least 5x (with an absolute wall-clock grace floor for machines
where both are too fast to time), and the backends must agree.

The grid runs inside a telemetry session, so ``BENCH_sim.json`` also
records the compiled backend's jit-cache hit rate and its
compile-vs-dispatch seconds split, and the session's event log lands next
to the JSON (``*_events.jsonl``, a CI artifact). A separate
``telemetry_overhead`` section times the headline flash-crowd round with
telemetry enabled vs disabled — ``check_bench.py`` gates the enabled run at
<= 5% slower.

    PYTHONPATH=src python benchmarks/sim_perf.py [--full] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro import compile_cache
from repro.core import get_shape
from repro.fleet import (Objective, PredictivePolicy, StaticPolicy,
                         evaluate_candidates, simulate, summarize, telemetry,
                         tiered_sla_workload)
from repro.fleet.workload import ServiceModel

# the scenario IS tune_controller's (one shared builder, so the gated
# "tune_controller-sized round" claim cannot drift out of lockstep)
from tune_controller import SEED, build_scenario as _tuner_scenario

HEADLINE = (24, 12, 3600.0)     # candidates x seeds x 720 bins (dt = 5 s)
GRID = ((8, 8, 720.0), HEADLINE)
GRID_FULL = GRID + ((48, 16, 3600.0),)
SUBSTEP_CELL = (8, 8, 720.0)    # fine-core cell: ~4x the per-bin work, so a
#                                 smaller slate keeps the numpy side timeable
N_SUBSTEPS = 4                  # the fidelity knob the fine-core gates run at
WARM_REPS = 3
OVERHEAD_REPS = 3               # telemetry on-vs-off repetitions (median)


def build_scenario(n_seeds: int, duration_s: float, backend: str,
                   n_substeps: int = 1, preemptive: bool = False):
    ts = _tuner_scenario(backend=backend, n_seeds=n_seeds,
                         duration_s=duration_s)
    if n_substeps != 1 or preemptive:
        ts = dataclasses.replace(ts, n_substeps=n_substeps,
                                 preemptive=preemptive)
    return ts


def bench_cell(n_candidates: int, n_seeds: int, duration_s: float,
               n_substeps: int = 1, preemptive: bool = False) -> dict:
    objective = Objective(min_attainment=1.0, penalty_usd_per_hour=1e5)
    candidates = PredictivePolicy.param_space().sample_lhs(n_candidates,
                                                          seed=SEED)
    ts_np = build_scenario(n_seeds, duration_s, "numpy", n_substeps,
                           preemptive)
    ts_jx = build_scenario(n_seeds, duration_s, "jax", n_substeps,
                           preemptive)
    n_bins = ts_np.workload.n_bins
    sims = n_candidates * n_seeds

    t0 = time.perf_counter()
    ev_np = evaluate_candidates(ts_np, candidates, objective)
    numpy_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ev_jx = evaluate_candidates(ts_jx, candidates, objective)
    jax_cold_s = time.perf_counter() - t0
    warm = []
    for _ in range(WARM_REPS):
        t0 = time.perf_counter()
        ev_jx = evaluate_candidates(ts_jx, candidates, objective)
        warm.append(time.perf_counter() - t0)
    jax_warm_s = float(np.median(warm))

    score_delta = max(float(np.abs(a.score - b.score).max())
                      for a, b in zip(ev_np, ev_jx))
    same_winner = (min(ev_np, key=lambda e: e.mean_score()).params
                   == min(ev_jx, key=lambda e: e.mean_score()).params)
    return {
        "n_candidates": n_candidates, "n_seeds": n_seeds, "n_bins": n_bins,
        "n_substeps": n_substeps, "preemptive": preemptive,
        "sims": sims,
        "numpy_s": numpy_s, "jax_cold_s": jax_cold_s,
        "jax_warm_s": jax_warm_s,
        "numpy_sims_per_s": sims / max(numpy_s, 1e-9),
        "jax_sims_per_s": sims / max(jax_warm_s, 1e-9),
        "speedup_warm": numpy_s / max(jax_warm_s, 1e-9),
        "speedup_cold": numpy_s / max(jax_cold_s, 1e-9),
        "max_score_delta": score_delta, "same_winner": bool(same_winner),
    }


# --------------------------- fidelity section -------------------------------

FIDELITY_GOLD_BAR = 0.95        # gold-class attainment bar for the sweep
FIDELITY_MAX_REPLICAS = 10

# service with a long fixed term relative to dt_sub (batches genuinely span
# substeps, so head-of-line blocking and preemption are visible) but a full
# batch still under the gold SLO: 0.5 + 16 * 0.0125 = 0.7 s vs 1.0 s gold
_FID_SERVICE = ("v5e-4", 0.5, 0.0125, 16)
_FID_RATE, _FID_DURATION, _FID_DT = 60.0, 600.0, 2.0
_FID_SEEDS, _FID_SEED = 4, 3

# the SimResult arrays the fine-core backend-agreement check compares; the
# substep engines are mirrored float-op-for-float-op, so the bar is 0.0
_FID_FIELDS = ("served", "queue", "latency_s", "ok_served", "utilization",
               "class_served", "class_ok", "class_queue", "preemptions",
               "preempted_work", "residue_work")


def _fidelity_workload():
    return tiered_sla_workload(_FID_RATE, _FID_DURATION, dt_s=_FID_DT,
                               n_seeds=_FID_SEEDS, seed=_FID_SEED)


def _fidelity_service():
    shape, t_fixed, t_unit, max_batch = _FID_SERVICE
    return ServiceModel("fidelity", get_shape(shape), t_fixed, t_unit,
                        max_batch)


def _fid_sim(wl, svc, replicas, disc, n_substeps, preemptive,
             backend="numpy"):
    return simulate(wl, svc, StaticPolicy(replicas), discipline=disc,
                    initial_replicas=replicas, backend=backend,
                    n_substeps=n_substeps, preemptive=preemptive)


def _fid_row(sim, replicas) -> dict:
    rep = summarize(sim)
    gold = rep.class_reports[0]
    return {
        "replicas": replicas,
        "gold_attainment": gold.attainment,
        "gold_p99_s": gold.p99_s,
        "p99_s": rep.p99_s,
        "worst_class_attainment": rep.worst_class_attainment(),
        "utilization": rep.mean_utilization,
        "usd_per_hour": rep.usd_per_hour,
        "preemptions": (float(sim.preemptions.sum())
                        if sim.preemptions is not None else 0.0),
    }


def bench_fidelity() -> dict:
    """Coarse-vs-fine fidelity at high utilization (the regime heavy traffic
    lives in), on a tiered-SLA flash crowd over a static fleet.

    Three pinned claims (gated by ``check_bench.py``):

    * at the >= 90%-utilization operating point the coarse bin-granular core
      *understates* p99 — the fine core's explicit head-of-line blocking
      pushes the tail out;
    * preemptive EDF meets the gold SLO bar at strictly lower $/hr than
      non-preemptive FIFO needs (FIFO must buy replicas to stop bronze's
      batches from blocking gold; EDF just interrupts them);
    * the fine core's numpy and jax engines agree *bit-exactly* (max field
      delta 0.0) on the operating-point run.
    """
    wl = _fidelity_workload()
    svc = _fidelity_service()

    # cheapest static fleet meeting the gold bar, per scheduling config
    def cheapest(disc, preemptive):
        for r in range(1, FIDELITY_MAX_REPLICAS + 1):
            sim = _fid_sim(wl, svc, r, disc, N_SUBSTEPS, preemptive)
            row = _fid_row(sim, r)
            if row["gold_attainment"] >= FIDELITY_GOLD_BAR:
                return row
        return None

    edf = cheapest("edf", True)
    fifo = cheapest("fifo", False)
    # the high-utilization operating point: the preemptive-EDF choice
    op_replicas = edf["replicas"] if edf else 3
    coarse = _fid_row(_fid_sim(wl, svc, op_replicas, "fifo", 1, False),
                      op_replicas)
    fine = _fid_row(_fid_sim(wl, svc, op_replicas, "fifo", N_SUBSTEPS, False),
                    op_replicas)

    # fine-core backend agreement at the operating point, bit-exact bar
    a = _fid_sim(wl, svc, op_replicas, "edf", N_SUBSTEPS, True,
                 backend="numpy")
    try:
        b = _fid_sim(wl, svc, op_replicas, "edf", N_SUBSTEPS, True,
                     backend="jax")
        max_delta = max(
            float(np.abs(np.asarray(getattr(a, f), float)
                         - np.asarray(getattr(b, f), float)).max())
            for f in _FID_FIELDS)
        agreement = {"max_field_delta": max_delta,
                     "bit_exact": max_delta == 0.0}
    except Exception as exc:          # no jax in this env: report, don't gate
        agreement = {"max_field_delta": None, "bit_exact": False,
                     "error": str(exc)}

    return {
        "scenario": (f"tiered-sla flash-crowd {_FID_RATE:g} req/s x "
                     f"{_FID_DURATION:g}s @ dt={_FID_DT:g}s, "
                     f"service {_FID_SERVICE}"),
        "n_substeps": N_SUBSTEPS,
        "gold_bar": FIDELITY_GOLD_BAR,
        "high_util": {
            "replicas": op_replicas,
            "utilization": fine["utilization"],
            "coarse_p99_s": coarse["p99_s"],
            "fine_p99_s": fine["p99_s"],
        },
        "headline": {
            "edf_preemptive": edf,
            "fifo": fifo,
            "fifo_at_edf_replicas": fine,
        },
        "agreement": agreement,
    }


def _jit_cache_stats(tel) -> dict:
    """Compiled-backend cache behaviour over the whole grid: jit-program
    cache hit rate and the compile-vs-dispatch wall-clock split (a cold
    dispatch pays XLA compilation on top of the steady-state dispatch cost
    its warm siblings measure)."""
    snap = tel.metrics.snapshot()
    core = snap["counter"].get("jaxsim_core_cache_total", {})
    disp = snap["counter"].get("jaxsim_dispatch_total", {})
    secs = snap["counter"].get("jaxsim_dispatch_seconds_total", {})
    hits = core.get("result=hit", 0.0)
    misses = core.get("result=miss", 0.0)
    n_cold = disp.get("kind=cold", 0.0)
    n_warm = disp.get("kind=warm", 0.0)
    cold_s = secs.get("kind=cold", 0.0)
    warm_s = secs.get("kind=warm", 0.0)
    warm_mean = warm_s / n_warm if n_warm else 0.0
    # compile_s: cold seconds beyond what those dispatches would have cost
    # at the steady-state (warm) rate
    compile_s = max(cold_s - n_cold * warm_mean, 0.0)
    return {
        "core_cache_hits": hits, "core_cache_misses": misses,
        "core_cache_hit_rate": hits / max(hits + misses, 1.0),
        "cold_dispatches": n_cold, "warm_dispatches": n_warm,
        "cold_dispatch_s": cold_s, "warm_dispatch_s": warm_s,
        "compile_s": compile_s, "dispatch_s": cold_s + warm_s - compile_s,
    }


def bench_telemetry_overhead(n_candidates: int, n_seeds: int,
                             duration_s: float,
                             reps: int = OVERHEAD_REPS) -> dict:
    """Best-of-``reps`` wall clock of the headline flash-crowd round with
    telemetry disabled vs enabled (fresh session per enabled rep, arms
    interleaved) — the <= 5% bar ``check_bench.py`` gates. Runs on the
    numpy backend: every candidate sim records its streams there, so it
    bounds the per-``SimResult`` recording cost the jax path shares."""
    objective = Objective(min_attainment=1.0, penalty_usd_per_hour=1e5)
    candidates = PredictivePolicy.param_space().sample_lhs(n_candidates,
                                                          seed=SEED)
    ts = build_scenario(n_seeds, duration_s, "numpy")

    def once(enabled: bool) -> float:
        if enabled:
            with telemetry.session():
                t0 = time.perf_counter()
                evaluate_candidates(ts, candidates, objective)
                return time.perf_counter() - t0
        t0 = time.perf_counter()
        evaluate_candidates(ts, candidates, objective)
        return time.perf_counter() - t0

    once(False)                         # warm caches before timing
    # interleave the arms and keep each arm's best rep: back-to-back pairs
    # see the same machine state, and min discards scheduler jitter that a
    # median over separated blocks folds into the ratio
    offs, ons = [], []
    for _ in range(reps):
        offs.append(once(False))
        ons.append(once(True))
    off, on = float(np.min(offs)), float(np.min(ons))
    return {
        "grid": f"{n_candidates}x{n_seeds}", "reps": reps,
        "disabled_s": off, "enabled_s": on,
        "overhead_frac": on / max(off, 1e-9) - 1.0,
    }


def run(full: bool = False) -> tuple:
    # the whole grid runs under one telemetry session: jit-cache hit/miss
    # and cold/warm dispatch-seconds accumulate for the report, and the
    # session's JSONL event log is the CI artifact. (Recording adds the very
    # overhead the telemetry_overhead section bounds at <= 5%, identically
    # to both backends' timings.)
    with telemetry.session() as tel:
        records = [bench_cell(*cell) for cell in (GRID_FULL if full else GRID)]
        records.append(bench_cell(*SUBSTEP_CELL, n_substeps=N_SUBSTEPS,
                                  preemptive=True))
    head = next(r for r in records
                if (r["n_candidates"], r["n_seeds"]) == HEADLINE[:2]
                and r["n_substeps"] == 1)
    sub = next(r for r in records if r["n_substeps"] == N_SUBSTEPS)
    overhead = bench_telemetry_overhead(*HEADLINE)
    fidelity = bench_fidelity()
    bench = {
        "benchmark": "sim_perf",
        "full": full,
        "scenario": "mset-surveil/flash-crowd (tune_controller build)",
        "policy_family": "predictive",
        "records": records,
        "headline": {
            "grid": f"{head['n_candidates']}x{head['n_seeds']}"
                    f"x{head['n_bins']}",
            "speedup": head["speedup_warm"],
            "speedup_cold": head["speedup_cold"],
            "numpy_s": head["numpy_s"],
            "jax_warm_s": head["jax_warm_s"],
            "jax_cold_s": head["jax_cold_s"],
            "compile_s": max(head["jax_cold_s"] - head["jax_warm_s"], 0.0),
        },
        "substep_headline": {
            "grid": f"{sub['n_candidates']}x{sub['n_seeds']}x{sub['n_bins']}"
                    f"@n={sub['n_substeps']}",
            "n_substeps": sub["n_substeps"],
            "preemptive": sub["preemptive"],
            "speedup": sub["speedup_warm"],
            "numpy_s": sub["numpy_s"],
            "jax_warm_s": sub["jax_warm_s"],
            "max_score_delta": sub["max_score_delta"],
        },
        "fidelity": fidelity,
        "jit_cache": _jit_cache_stats(tel),
        "telemetry_overhead": overhead,
        "agreement": {
            "max_score_delta": max(r["max_score_delta"] for r in records
                                   if r["n_substeps"] == 1),
            "same_winner": all(r["same_winner"] for r in records),
        },
    }
    return bench, tel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="add the 48x16x720 cell")
    ap.add_argument("--out", default="BENCH_sim.json",
                    help="JSON results path (CI uploads this artifact)")
    args = ap.parse_args()
    compile_cache.place()
    bench, tel = run(full=args.full)
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2)
    events_path = os.path.splitext(args.out)[0] + "_events.jsonl"
    n_events = tel.export_jsonl(events_path)
    hdr = (f"{'cands':>6} {'seeds':>6} {'bins':>6} {'numpy':>9} "
           f"{'jax cold':>9} {'jax warm':>9} {'speedup':>8}")
    print(hdr)
    for r in bench["records"]:
        print(f"{r['n_candidates']:>6} {r['n_seeds']:>6} {r['n_bins']:>6} "
              f"{r['numpy_s']:>8.2f}s {r['jax_cold_s']:>8.2f}s "
              f"{r['jax_warm_s']:>8.3f}s {r['speedup_warm']:>7.1f}x")
    h = bench["headline"]
    print(f"\nheadline ({h['grid']}): {h['speedup']:.1f}x warm "
          f"({h['numpy_s']:.2f}s numpy vs {h['jax_warm_s']:.3f}s jax; "
          f"cold {h['jax_cold_s']:.2f}s, ~{h['compile_s']:.2f}s compile), "
          f"max score delta {bench['agreement']['max_score_delta']:.2e}")
    jc = bench["jit_cache"]
    print(f"jit cache: {jc['core_cache_hit_rate'] * 100:.0f}% hit rate "
          f"({jc['core_cache_hits']:.0f} hits / "
          f"{jc['core_cache_misses']:.0f} misses), "
          f"{jc['cold_dispatches']:.0f} cold + "
          f"{jc['warm_dispatches']:.0f} warm dispatches, "
          f"compile {jc['compile_s']:.2f}s vs dispatch "
          f"{jc['dispatch_s']:.2f}s")
    s = bench["substep_headline"]
    print(f"substep ({s['grid']}, preemptive): {s['speedup']:.1f}x warm "
          f"({s['numpy_s']:.2f}s numpy vs {s['jax_warm_s']:.3f}s jax), "
          f"max score delta {s['max_score_delta']:.2e}")
    fid = bench["fidelity"]
    hu, hl = fid["high_util"], fid["headline"]
    edf, fifo = hl["edf_preemptive"], hl["fifo"]
    print(f"fidelity ({fid['scenario']}, n_substeps={fid['n_substeps']}): "
          f"coarse p99 {hu['coarse_p99_s']:.1f}s vs fine "
          f"{hu['fine_p99_s']:.1f}s at util {hu['utilization']:.2f}")
    print(f"  gold bar {fid['gold_bar']:.2f}: preemptive EDF "
          f"{edf['replicas']} replicas ${edf['usd_per_hour']:.1f}/h "
          f"(attain {edf['gold_attainment']:.3f}) vs FIFO "
          f"{fifo['replicas']} replicas ${fifo['usd_per_hour']:.1f}/h")
    ag = fid["agreement"]
    if ag.get("error"):
        print(f"  fine-core backend agreement skipped: {ag['error']}")
    else:
        print(f"  fine-core numpy vs jax: max field delta "
              f"{ag['max_field_delta']:.2e} "
              f"(bit exact: {ag['bit_exact']})")
    ov = bench["telemetry_overhead"]
    print(f"telemetry overhead ({ov['grid']} numpy round): "
          f"{ov['disabled_s']:.2f}s off vs {ov['enabled_s']:.2f}s on "
          f"({ov['overhead_frac'] * 100:+.1f}%)")
    print(f"wrote {args.out} and {events_path} ({n_events} events)")


if __name__ == "__main__":
    main()
