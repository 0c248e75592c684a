"""Scoping-oracle benchmark: offline tuner sweeps compiled into a
microsecond-latency lookup service, pinned end to end.

The experiment reuses the closed-loop benchmark's world (tuned PI
autoscaler on the MSET serving fleet, diurnal live trace, 2x mid-trace
service degradation) and adds the oracle on top:

* **build** — sweep ``tune()`` over a (mean rate x burstiness x SLO) grid
  of canonical traces on the nominal fleet and compile the winners into an
  ``OracleTable`` (the CI artifact);
* **query** — answer a held-out flash-crowd trace the sweep never saw;
  gate the measured latency (median <= 1 ms) and the *regret*: the
  oracle's config, freshly simulated, must score within 10% of a from-
  scratch ``tune()`` on that trace at the same attainment bar;
* **verify** — spot-check interior query points against fresh simulation
  (``verify_oracle``), pinning the oracle's cost-prediction error bound;
* **closed loop** — run the PR 8 headline drift case twice, warm re-tune
  alone vs oracle-first, and gate that the oracle arm recovers no later
  (and at the same segment, no costlier) while spending a fraction of the
  re-tune's simulations.

Results land in ``BENCH_oracle.json``; the compiled table in
``oracle_table.json`` (both CI artifacts).

    PYTHONPATH=src python benchmarks/oracle.py [--full] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from closed_loop import (ATTAIN_BAR, COLD_BINS, DRIFT_FACTOR, DT_S, SEED,
                         TUNE_BAR, build)

from repro import compile_cache
from repro.fleet import (ClosedLoopController, Objective, PIPolicy,
                         TuningBudget, TuningScenario, Workload,
                         evaluate_candidates, flash_crowd_trace, tune,
                         window_metrics)
from repro.fleet.oracle import (OracleGrid, OracleTable, ScopingOracle,
                                build_oracle, featurize, query_latency_us,
                                verify_oracle)

ORACLE_SEED = 7
HELDOUT_SEED = 4242         # trace seed the sweep never saw
GRID_DURATION_S = 1800.0
BURST_AXIS = (1.0, 1.6, 2.2)    # brackets the diurnal tail (~1.5) and the
#                                 held-out flash crowd (~1.8)
SLO_AXIS = (1.0, 2.0, 4.0)


def build_table(ts: TuningScenario, objective: Objective, *,
                full: bool, backend: str) -> OracleTable:
    """Sweep the grid on the *nominal* fleet: the closed loop maps a
    degraded world onto it by inflating the query's rate axis."""
    svc = ts.fleet.pools[0].service
    mt = svc.max_throughput
    grid = OracleGrid(
        mean_rates=(1.5 * mt, 3.0 * mt, 6.0 * mt, 12.0 * mt),
        burstiness=BURST_AXIS, slos=SLO_AXIS,
        duration_s=GRID_DURATION_S, dt_s=DT_S,
        n_seeds=4 if full else 3, seed=ORACLE_SEED)
    return build_oracle(
        grid, ts.fleet, PIPolicy, PIPolicy.param_space(),
        objective=objective,
        budget=TuningBudget(n_candidates=14 if full else 10, init_seeds=2),
        context=ts.context, max_queue=ts.max_queue, backend=backend,
        name="mset-oracle")


def heldout_flash_crowd(ts: TuningScenario, *, full: bool):
    """A flash-crowd trace strictly interior to the grid: mean rate between
    columns, burstiness ~1.8 between rows, fresh Monte Carlo seeds."""
    svc = ts.fleet.pools[0].service
    mt = svc.max_throughput
    tr = flash_crowd_trace(
        3.1 * mt, GRID_DURATION_S, dt_s=DT_S, peak_mult=2.4,
        burst_width_s=GRID_DURATION_S / 14, n_seeds=6 if full else 4,
        seed=HELDOUT_SEED)
    return Workload.from_trace(tr, float(ts.context["slo_s"]))


def heldout_regret(ts: TuningScenario, oracle: ScopingOracle,
                   objective: Objective, *, full: bool,
                   backend: str) -> dict:
    """Oracle answer vs a from-scratch tune() on the held-out trace, both
    freshly simulated on the same paired draws."""
    wl = heldout_flash_crowd(ts, full=full)
    ans = oracle.query(wl)
    if not ans.ok:
        return {"error": f"oracle refused the held-out trace: {ans.reason}",
                "features": ans.features.as_dict() if ans.features else None}
    scen = TuningScenario(
        name="heldout/flash-crowd", workload=wl, fleet=ts.fleet,
        policy_cls=PIPolicy, context=ts.context, max_queue=ts.max_queue,
        backend=backend)
    fresh = tune(scen, PIPolicy.param_space(), objective,
                 TuningBudget(n_candidates=14 if full else 12,
                              init_seeds=2), seed=SEED)
    evs = evaluate_candidates(scen, [dict(ans.params),
                                     dict(fresh.winner.params)], objective)
    o_ev, f_ev = evs
    regret = max(0.0, (o_ev.mean_score() - f_ev.mean_score())
                 / max(f_ev.mean_score(), 1e-9))
    return {
        "attainment_bar": ATTAIN_BAR,
        "features": ans.features.as_dict(),
        "oracle": {"params": dict(ans.params),
                   "cost_usd_hr": o_ev.mean_cost(),
                   "attainment": o_ev.mean_attainment(),
                   "score": o_ev.mean_score(),
                   "predicted_cost_usd_hr": ans.cost_usd_hr,
                   "latency_us": ans.latency_us, "exact": ans.exact},
        "fresh": {"params": dict(fresh.winner.params),
                  "cost_usd_hr": f_ev.mean_cost(),
                  "attainment": f_ev.mean_attainment(),
                  "score": f_ev.mean_score(),
                  "sims_used": fresh.sims_used},
        "regret": regret,
        "scenario": scen,         # reused by the agreement check (popped)
    }


def backend_agreement(ts: TuningScenario, heldout: dict,
                      objective: Objective) -> dict:
    """numpy vs jax on the held-out oracle evaluation: the answer the
    oracle ships must score the same on both simulator backends."""
    try:
        import jax  # noqa: F401
    except Exception as exc:            # pragma: no cover - no-jax machines
        return {"error": f"jax unavailable: {exc}"}
    scen = heldout.get("scenario")
    params = heldout.get("oracle", {}).get("params")
    if scen is None or params is None:
        return {"error": "held-out evaluation unavailable"}
    scores = {}
    for backend in ("numpy", "jax"):
        scen.backend = backend
        scores[backend] = evaluate_candidates(
            scen, [dict(params)], objective)[0].mean_score()
    return {"backends": ["numpy", "jax"],
            "numpy_score": scores["numpy"], "jax_score": scores["jax"],
            "max_score_delta": abs(scores["numpy"] - scores["jax"])}


def _arm_record(res, td: int, T: int) -> dict:
    swaps = [e.t_bin for e in res.events if e.kind == "swap"]
    post = window_metrics(res.sim, td, T)
    t_rec = min(swaps[0] + COLD_BINS, T - 1) if swaps else td
    rec = window_metrics(res.sim, t_rec, T)
    return {
        "swap_bin": swaps[0] if swaps else None,
        "n_alarms": res.n_alarms, "n_swaps": res.n_swaps,
        "post_drift_attainment": post.worst_class_attainment,
        "post_drift_usd_per_hour": post.usd_per_hour,
        "recovery_attainment": rec.worst_class_attainment,
        "active_params": res.active_params,
    }


def closed_loop_comparison(ts, case, incumbent, oracle: ScopingOracle,
                           objective: Objective, *, full: bool) -> dict:
    """The same drift case through both drift-response arms: warm re-tune
    alone (PR 8 behaviour) vs oracle-first with re-tune fallback."""
    td = case.drift_bins()[0]
    T = case.n_bins
    kw = dict(segment_bins=15,
              retune_budget=TuningBudget(n_candidates=16 if full else 14,
                                         init_seeds=2),
              objective=objective)
    res_rt = ClosedLoopController(ts, incumbent, **kw).run(case)
    res_or = ClosedLoopController(ts, incumbent, oracle=oracle, **kw).run(case)
    rt, orc = _arm_record(res_rt, td, T), _arm_record(res_or, td, T)
    rt["tune_sims"] = sum(r.sims_used for r in res_rt.retunes)
    # an oracle consultation costs one paired <= 3-candidate evaluation at
    # the live workload's full replicate budget per hit, plus any fallback
    # re-tunes on misses
    orc["hits"] = res_or.oracle_hits
    orc["misses"] = res_or.oracle_misses
    orc["consult_sims"] = (
        sum(e.detail.get("eval_sims", 0) for e in res_or.events
            if e.kind == "oracle-hit")
        + sum(r.sims_used for r in res_or.retunes))
    orc["query_latency_us"] = [round(a.latency_us, 1)
                               for a in res_or.oracle_answers]
    return {"attainment_bar": ATTAIN_BAR, "segment_bins": 15,
            "drift_bin": td, "n_bins": T,
            "retune": rt, "oracle": orc}


def run(full: bool = False, backend: str = "auto",
        table_out: str = None):
    t_start = time.perf_counter()
    ts, case = build(full, backend=backend)
    objective = Objective(min_attainment=TUNE_BAR,
                          penalty_usd_per_hour=2000.0)
    incumbent = tune(ts, PIPolicy.param_space(), objective,
                     TuningBudget(n_candidates=16 if full else 12,
                                  init_seeds=2), seed=SEED)

    t0 = time.perf_counter()
    table = build_table(ts, objective, full=full, backend=backend)
    build_wall = time.perf_counter() - t0
    if table_out:
        table.save(table_out)
    oracle = ScopingOracle(table)

    latency = query_latency_us(
        oracle, featurize(case.workload.total_trace()),
        float(ts.context["slo_s"]), n=200)
    heldout = heldout_regret(ts, oracle, objective, full=full,
                             backend=backend)
    agreement = backend_agreement(ts, heldout, objective)
    heldout.pop("scenario", None)
    verify = verify_oracle(table, ts.fleet, PIPolicy,
                           n_samples=5 if full else 3, seed=ORACLE_SEED,
                           context=ts.context, max_queue=ts.max_queue,
                           backend=backend)
    cl = closed_loop_comparison(ts, case, incumbent, oracle, objective,
                                full=full)

    bench = {
        "benchmark": "scoping_oracle",
        "full": full,
        "backend": backend,
        "scenario": ts.name,
        "build": dict(table.build_info,
                      grid_shape=list(table.grid.shape),
                      wall_clock_s=build_wall),
        "latency": latency,
        "heldout": heldout,
        "agreement": agreement,
        "verify": verify.to_json(),
        "closed_loop": cl,
        "drift": {"factor": DRIFT_FACTOR, "dt_s": DT_S},
        "wall_clock_s": time.perf_counter() - t_start,
    }
    return table, bench


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_oracle.json",
                    help="JSON results path (CI uploads this artifact)")
    ap.add_argument("--table-out", default="oracle_table.json",
                    help="compiled OracleTable artifact path")
    ap.add_argument("--backend", default="auto",
                    choices=("numpy", "jax", "auto"))
    args = ap.parse_args()
    compile_cache.place()
    table, bench = run(full=args.full, backend=args.backend,
                       table_out=args.table_out)
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=float)
    print(table.summary())
    lat, ho, cl = bench["latency"], bench["heldout"], bench["closed_loop"]
    print(f"query latency {lat['median_us']:.0f}us median / "
          f"{lat['p99_us']:.0f}us p99; held-out regret "
          f"{ho.get('regret', float('nan')) * 100:.1f}% "
          f"(oracle ${ho.get('oracle', {}).get('cost_usd_hr', 0):.2f}/hr @ "
          f"{ho.get('oracle', {}).get('attainment', 0):.4f})")
    print(f"drift recovery: oracle swap bin "
          f"{cl['oracle']['swap_bin']} ({cl['oracle']['consult_sims']} "
          f"sims) vs re-tune bin {cl['retune']['swap_bin']} "
          f"({cl['retune']['tune_sims']} sims)")
    print(bench["verify"] and
          f"verify: {bench['verify']['n']} spot-checks, max cost err "
          f"{bench['verify']['max_cost_err'] * 100:.1f}%")
    print(f"wrote {args.out} (wall clock {bench['wall_clock_s']:.1f}s)")


if __name__ == "__main__":
    main()
