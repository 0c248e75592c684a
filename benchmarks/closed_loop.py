"""Closed-loop autonomous control benchmark: drift-triggered re-scope +
warm re-tune + mid-trace policy hot-swap, pinned end to end.

The experiment: a PI autoscaler is autonomously tuned for the nominal MSET
serving fleet (the incumbent), then serves a fresh diurnal trace on which
every pool's service times silently inflate by ``DRIFT_FACTOR`` at the
midpoint — the paper's degrading-node scenario. Three deployments ride the
same drifted world:

* **incumbent (static config)** — the tuned PI rides through unchanged; its
  anti-windup clamp bounds its authority, so it cannot re-center and the
  worst-class attainment collapses below the bar;
* **closed loop** — ``ClosedLoopController`` detects the drift from
  telemetry (MSET+SPRT probe), re-scopes the shape choice under the
  degraded service model, warm re-tunes the PI on the remaining workload
  (seeded from the incumbent report, compiled backend), and hot-swaps the
  winner mid-trace;
* **static-after-drift** — the counterfactual ops response: the cheapest
  ``StaticPolicy`` fleet that restores the attainment bar over the
  post-drift window (peak-provisioned, since a static fleet cannot follow
  the diurnal valleys).

Headline (gated by ``tools/check_bench.py`` against
``benchmarks/baselines/control.json``):

* the incumbent really breaks: post-drift worst-class attainment < bar;
* the closed loop recovers: worst-class attainment >= ``ATTAIN_BAR`` (0.95)
  over the post-swap window;
* it recovers *cheaper* than the static response: closed-loop post-drift
  $/hr < the cheapest bar-restoring static fleet's $/hr;
* the warm re-tune is backend-exact: numpy and jax agree on the re-tune
  winner and its score.

Results land in ``BENCH_control.json`` (CI artifact).

    PYTHONPATH=src python benchmarks/closed_loop.py [--full] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compile_cache
from repro.core.recommender import recommend
from repro.fleet import (ClosedLoopController, FleetConfig, Objective,
                         PIPolicy, SegmentedSimulation, StaticPolicy,
                         TuningBudget, diurnal_trace, mset_scenario,
                         simulate_fleet, tune, tuning_scenario,
                         window_metrics)
from repro.fleet.control import service_degradation_case, tail_workload
from repro.fleet.telemetry.drift import degrade_fleet
from repro.fleet.workload import Workload

SEED = 0
COLD_START_S = 60.0
DT_S = 10.0
COLD_BINS = int(COLD_START_S / DT_S)    # actuation dead time, in bins
TUNE_BAR = 0.96         # tune with margin above the gated bar: the live
#                         trace is a fresh draw the tuner never saw
QUOTA = 24
DRIFT_FACTOR = 2.0
ATTAIN_BAR = 0.95
SEGMENT_BINS = 15       # control cadence: probe needs >= its min_alarm_bins
MEAN_MULT = 3.0         # mean arrival rate, in single-replica throughputs
AMPLITUDE = 0.4         # diurnal swing; trough stays above 1 replica's worth
T_DRIFT_FRAC = 0.25     # drift lands at the diurnal peak: the incumbent
#                         breaks immediately, the static recovery must hold
#                         the degraded peak for the whole window, and the
#                         closed loop rides the valley back down


def build(full: bool = False, backend: str = "auto"):
    """Nominal tuning scenario + the drifted live case. The diurnal trace is
    the honest feedback-vs-static setting: the PI follows the valleys while
    a static fleet must hold the peak."""
    scenario = mset_scenario(n_signals=1024, n_memvec=4096, fleet=8,
                             slo_s=2.0)
    shape = recommend(scenario.rows_at(), scenario.constraint()).shape.name
    svc = scenario.service_for(shape)
    duration = 7200.0 if full else 3600.0
    n_seeds = 8 if full else 6
    mean_rate = MEAN_MULT * svc.max_throughput
    mc = diurnal_trace(mean_rate, duration, dt_s=DT_S, amplitude=AMPLITUDE,
                       period_s=duration, n_seeds=n_seeds, seed=SEED + 1)
    live = diurnal_trace(mean_rate, duration, dt_s=DT_S, amplitude=AMPLITUDE,
                         period_s=duration, n_seeds=4, seed=SEED + 101)
    # admission control: bound the backlog at ~2 bins of mean demand so an
    # under-provisioned fleet sheds (SLO misses) instead of queueing forever
    fleet = FleetConfig((scenario.pool_for(shape, cold_start_s=COLD_START_S,
                                           max_replicas=QUOTA),),
                        max_queue=2.0 * mean_rate * DT_S)
    ts = tuning_scenario(scenario, mc, PIPolicy, fleet=fleet,
                         cold_start_s=COLD_START_S, backend=backend,
                         name="mset-diurnal/pi")
    case = service_degradation_case(
        Workload.from_trace(live, scenario.slo_s), fleet,
        factor=DRIFT_FACTOR, t_drift_frac=T_DRIFT_FRAC)
    return ts, case


def _window_record(wm):
    return {"t0": wm.t0, "t1": wm.t1,
            "worst_class_attainment": wm.worst_class_attainment,
            "usd_per_hour": wm.usd_per_hour,
            "mean_replicas": wm.mean_replicas}


def cheapest_static_recovery(ts, case, td: int):
    """The counterfactual ops response: smallest (cheapest) static fleet
    restoring the attainment bar on the degraded post-drift tail."""
    wl = tail_workload(case.workload, td)
    fleet = degrade_fleet(case.fleet, DRIFT_FACTOR)
    for n in range(1, QUOTA + 1):
        sim = simulate_fleet(wl, fleet, StaticPolicy(n),
                             cold_start_seed=ts.cold_start_seed)
        wm = window_metrics(sim, 0)
        if wm.worst_class_attainment >= ATTAIN_BAR:
            return n, wm
    return None, None


def retune_agreement(ctl, res, td: int):
    """Backend agreement on the drift response itself: re-run the first
    warm re-tune on both simulator backends and compare winner + score."""
    try:
        import jax  # noqa: F401
    except Exception as exc:            # pragma: no cover - no-jax machines
        return {"error": f"jax unavailable: {exc}"}
    if not res.retunes:
        return {"error": "closed loop never re-tuned"}
    t1 = next(e.t_bin for e in res.events if e.kind == "retune")
    factor = next(e.detail["est_factor"] for e in res.events
                  if e.kind == "drift-alarm")
    out = {}
    for backend in ("numpy", "jax"):
        scen = ctl._tail_scenario(t1, factor)
        scen.backend = backend
        report = tune(scen, ctl.incumbent.space, ctl.objective,
                      ctl.retune_budget, seed=ctl.retune_seed,
                      warm_start=ctl.incumbent, warm_jitter=ctl.retune_jitter,
                      baseline=dict(ctl.incumbent_params))
        out[backend] = report
    wn = out["numpy"].winner
    wj = out["jax"].winner
    return {
        "backends": ["numpy", "jax"],
        "same_winner": wn.params == wj.params,
        "numpy_winner": wn.params,
        "jax_winner": wj.params,
        "max_score_delta": abs(wn.mean_score() - wj.mean_score()),
    }


def run(full: bool = False, backend: str = "auto"):
    t_start = time.perf_counter()
    ts, case = build(full, backend=backend)
    objective = Objective(min_attainment=TUNE_BAR,
                          penalty_usd_per_hour=2000.0)
    incumbent = tune(ts, PIPolicy.param_space(), objective,
                     TuningBudget(n_candidates=16 if full else 12,
                                  init_seeds=2), seed=SEED)
    td = case.drift_bins()[0]
    T = case.n_bins

    # the incumbent riding through the drift unchanged (no controller)
    ride_sim = SegmentedSimulation(case.workload, case.fleet,
                                   ts.make_policy(incumbent.winner.params),
                                   cold_start_seed=ts.cold_start_seed)
    ride_sim.run_until(td)
    ride_sim.swap(fleet=degrade_fleet(case.fleet, DRIFT_FACTOR))
    ride = ride_sim.run_until(T).result()
    inc_pre = window_metrics(ride, 0, td)
    inc_post = window_metrics(ride, td, T)

    ctl = ClosedLoopController(
        ts, incumbent, segment_bins=SEGMENT_BINS,
        retune_budget=TuningBudget(n_candidates=16 if full else 14,
                                   init_seeds=2),
        objective=objective)
    res = ctl.run(case)
    cl_pre = window_metrics(res.sim, 0, td)
    cl_post = window_metrics(res.sim, td, T)
    # recovery is judged once the swapped-in config's ordered capacity has
    # landed: swap bin + the cold-start dead time (physical actuation lag)
    swaps = [e.t_bin for e in res.events if e.kind == "swap"]
    t_rec = min(swaps[0] + COLD_BINS, T - 1) if swaps else td
    cl_rec = window_metrics(res.sim, t_rec, T)
    first_alarm = next((e.t_bin for e in res.events
                        if e.kind == "drift-alarm"), -1)

    n_static, static_wm = cheapest_static_recovery(ts, case, td)
    agreement = retune_agreement(ctl, res, td)

    recovered = cl_rec.worst_class_attainment >= ATTAIN_BAR
    incumbent_breaks = inc_post.worst_class_attainment < ATTAIN_BAR
    cheaper = (static_wm is not None
               and cl_post.usd_per_hour < static_wm.usd_per_hour)
    bench = {
        "benchmark": "closed_loop_control",
        "full": full,
        "backend": backend,
        "scenario": ts.name,
        "drift": {"factor": DRIFT_FACTOR, "t_bin": td, "n_bins": T,
                  "segment_bins": SEGMENT_BINS},
        "incumbent": {
            "params": incumbent.winner.params,
            "pre_drift": _window_record(inc_pre),
            "post_drift": _window_record(inc_post),
        },
        "closed_loop": {
            "n_alarms": res.n_alarms,
            "n_swaps": res.n_swaps,
            "est_factor": res.est_factor,
            "first_alarm_bin": first_alarm,
            "detection_delay_bins": (first_alarm - td if first_alarm >= 0
                                     else None),
            "active_params": res.active_params,
            "pre_drift": _window_record(cl_pre),
            "post_drift": _window_record(cl_post),
            "recovery": _window_record(cl_rec),
            "rescoped_feasible": bool(res.rescopes
                                      and res.rescopes[0].shape is not None),
            "timeline": [{"t_bin": e.t_bin, "kind": e.kind}
                         for e in res.events],
        },
        "static_after_drift": (
            dict(_window_record(static_wm), n_replicas=n_static)
            if static_wm is not None else None),
        "headline": {
            "attainment_bar": ATTAIN_BAR,
            "incumbent_breaks": bool(incumbent_breaks),
            "recovered": bool(recovered),
            "recovery_attainment": cl_rec.worst_class_attainment,
            "closed_loop_usd_per_hour": cl_post.usd_per_hour,
            "static_usd_per_hour": (static_wm.usd_per_hour
                                    if static_wm else None),
            "cheaper_than_static": bool(cheaper),
        },
        "agreement": agreement,
        "wall_clock_s": time.perf_counter() - t_start,
    }
    return res, bench


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_control.json",
                    help="JSON results path (CI uploads this artifact)")
    ap.add_argument("--backend", default="auto",
                    choices=("numpy", "jax", "auto"))
    args = ap.parse_args()
    compile_cache.place()
    res, bench = run(full=args.full, backend=args.backend)
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=float)
    h = bench["headline"]
    print(res.timeline())
    print(f"\nincumbent post-drift attainment "
          f"{bench['incumbent']['post_drift']['worst_class_attainment']:.4f}"
          f" (breaks: {h['incumbent_breaks']}); closed loop recovers to "
          f"{h['recovery_attainment']:.4f} (bar {h['attainment_bar']}) at "
          f"${h['closed_loop_usd_per_hour']:.2f}/hr vs static recovery "
          f"${h['static_usd_per_hour']}/hr "
          f"(cheaper: {h['cheaper_than_static']})")
    print(f"wrote {args.out} (wall clock {bench['wall_clock_s']:.1f}s)")


if __name__ == "__main__":
    main()
