"""Fleet-scaling sweep: policies x traces x fleet configurations (homogeneous
per-shape fleets AND mixed-shape fleets), under per-instance-type cloud
quotas, plus a tiered-SLA multi-class sweep across scheduling disciplines.

For each homogeneous candidate shape, replicas of that shape serve the same
traces — the four synthetic ``standard_traces`` plus the bundled
Azure-Functions-style day replayed via ``load_trace_csv`` — under each
autoscaling policy; a mixed v5e-4+v5e-16 fleet runs the
heterogeneous predictive policy against the same traces. Every pool is capped
at ``QUOTA`` replicas (clouds limit instance counts per type), which is what
makes the comparison honest: a flash crowd can outgrow the small shape's
quota, and a big-shape-only fleet overpays at baseline — the mixed fleet
splits the difference.

The tiered-SLA sweep serves a gold/silver/bronze mixed-class flash-crowd
workload under FIFO, strict priority, and EDF, sweeping static fleet sizes to
the cheapest one meeting *every* class's SLO: the headline is that
EDF/priority meet the tiered SLOs at measurably lower cost than
capacity-equivalent FIFO (which must be provisioned for the peak because gold
queues behind bronze backlog).

Results land in ``BENCH_fleet.json`` (CI artifact); ``tools/check_bench.py``
gates PRs against the committed baseline in ``benchmarks/baselines/``.

    PYTHONPATH=src python benchmarks/fleet_scaling.py [--full] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compile_cache
from repro.fleet import (HeterogeneousPredictivePolicy, StaticPolicy,
                         class_table, comparison_table,
                         cost_efficiency_table, default_policies,
                         load_trace_csv, mset_scenario, simulate,
                         simulate_fleet, standard_traces, summarize,
                         tiered_sla_workload)

QUOTA = 16              # max replicas per pool (per-instance-type quota)
COLD_START_S = 60.0
MIXED_SHAPES = ("v5e-4", "v5e-16")
DISCIPLINE_SWEEP = ("fifo", "priority", "edf")
TIERED_ATTAINMENT_BAR = 0.99    # every class must clear this
REPLAY_CSV = os.path.join(os.path.dirname(__file__), "data",
                          "azure_functions_day.csv")


def replay_traces(mean_rate: float, n_seeds: int):
    """The bundled Azure-Functions-style day, rescaled so its mean matches
    the synthetic traces' sustained rate — the ROADMAP's first real-trace
    step, riding the same policy x shape sweep as ``standard_traces``."""
    return [load_trace_csv(REPLAY_CSV, rate_col="requests_per_s", dt_s=300.0,
                           mean_rate_per_s=mean_rate, n_seeds=n_seeds,
                           seed=11, name="azure-day")]


def _record(report, sim, wall_s):
    return {
        "policy": report.policy,
        "discipline": report.discipline,
        "trace": report.trace,
        "shapes": report.shape,
        "pools": [{"shape": p.service.shape.name,
                   "cold_start_s": p.cold_start_s,
                   "max_replicas": p.max_replicas}
                  for p in sim.fleet.pools],
        "slo_s": report.slo_s,
        "slo_attainment": report.slo_attainment,
        "p50_s": report.p50_s,
        "p99_s": report.p99_s,
        "drop_rate": report.drop_rate,
        "mean_billed_replicas": report.mean_replicas,
        "usd_per_hour": report.usd_per_hour,
        "wall_clock_s": wall_s,
    }


def run(full: bool = False, scenario=None):
    scenario = scenario or mset_scenario(n_signals=1024, n_memvec=4096,
                                         fleet=8, slo_s=1.0)
    shape_names = [r.shape_name for r in scenario.rows_at()]
    if not full:
        shape_names = shape_names[:4]
    # standard_traces scales the flash-crowd burst width as duration/30; keep
    # it a few cold-start periods wide, or no policy can outrun the burst
    duration = 7200.0 if full else 3600.0
    n_seeds = 16 if full else 8
    base_thr = scenario.service_for(scenario.cheapest_shape()).max_throughput
    # ~9 small-shape replicas of sustained demand: the flash-crowd peak
    # (4x mean) then needs ~36 — past the small shapes' quota
    mean_rate = 9.0 * base_thr
    reports, records = [], []

    def _run(trace, make_sim):
        t0 = time.perf_counter()
        sim = make_sim(trace)
        wall = time.perf_counter() - t0
        rep = summarize(sim)
        reports.append(rep)
        records.append(_record(rep, sim, wall))

    for shape_name in shape_names:
        service = scenario.service_for(shape_name)
        # restrict scoping rows to the swept shape so the predictive policy's
        # recommend() call sizes against it
        rows = [r for r in scenario.rows if r.shape_name == shape_name]
        try:
            policies = default_policies(
                rows, scenario.constraint(), scenario.units_per_step,
                static_replicas=min(
                    int(mean_rate / (0.85 * service.max_throughput)) + 1,
                    QUOTA),
                cold_start_s=COLD_START_S)
        except ValueError:            # shape infeasible for the SLO
            continue
        for trace in (standard_traces(mean_rate, duration, dt_s=5.0,
                                      n_seeds=n_seeds)
                      + replay_traces(mean_rate, n_seeds)):
            for policy in policies:   # simulate() resets policy state
                _run(trace, lambda tr, p=policy, s=service: simulate(
                    tr, s, p, slo_s=scenario.slo_s,
                    cold_start_s=COLD_START_S, max_replicas=QUOTA))

    # the mixed fleet: fine-grained baseline + coarse burst capacity
    fleet = scenario.fleet_for(list(MIXED_SHAPES), cold_start_s=COLD_START_S,
                               max_replicas=QUOTA)
    hetero = HeterogeneousPredictivePolicy(
        scenario.rows, scenario.constraint(), scenario.units_per_step, fleet,
        horizon_s=2 * COLD_START_S)
    for trace in (standard_traces(mean_rate, duration, dt_s=5.0,
                                  n_seeds=n_seeds)
                  + replay_traces(mean_rate, n_seeds)):
        _run(trace, lambda tr: simulate_fleet(tr, fleet, hetero,
                                              slo_s=scenario.slo_s))
    return reports, records


def _class_record(report, n_replicas):
    return {
        "discipline": report.discipline,
        "replicas": n_replicas,
        "usd_per_hour": report.usd_per_hour,
        "worst_class_attainment": report.worst_class_attainment(),
        "class_attainment": {c.name: c.attainment
                             for c in report.class_reports},
        "class_p99_s": {c.name: c.p99_s for c in report.class_reports},
    }


def run_tiered(full: bool = False, scenario=None):
    """Tiered-SLA mixed-class sweep: for each discipline, the cheapest static
    fleet meeting every class SLO at >= ``TIERED_ATTAINMENT_BAR``; plus FIFO
    evaluated at the EDF winner's capacity (the capacity-equivalent
    comparison the headline rests on)."""
    scenario = scenario or mset_scenario(n_signals=1024, n_memvec=4096,
                                         fleet=8, slo_s=1.0)
    service = scenario.service_for(scenario.cheapest_shape())
    duration = 7200.0 if full else 3600.0
    n_seeds = 16 if full else 8
    wl = tiered_sla_workload(6.0 * service.max_throughput, duration,
                             dt_s=5.0, n_seeds=n_seeds, seed=3)
    cheapest = {}                 # discipline -> (n, report)
    by_n = {}                     # (discipline, n) -> report
    for disc in DISCIPLINE_SWEEP:
        for n in range(2, QUOTA + 1):
            rep = summarize(simulate(wl, service, StaticPolicy(n),
                                     discipline=disc, initial_replicas=n,
                                     max_replicas=QUOTA))
            by_n[(disc, n)] = rep
            if rep.worst_class_attainment() >= TIERED_ATTAINMENT_BAR:
                cheapest[disc] = (n, rep)
                break
    summary = {
        "workload": {
            "tiers": [{"name": c.name, "slo_s": c.slo_s,
                       "priority": c.priority} for c in wl.classes],
            "base_rate_per_s": 6.0 * service.max_throughput,
            "duration_s": duration,
            "n_seeds": n_seeds,
        },
        "shape": service.shape.name,
        "attainment_bar": TIERED_ATTAINMENT_BAR,
        "cheapest_feasible": {d: _class_record(rep, n)
                              for d, (n, rep) in cheapest.items()},
    }
    # capacity-equivalent FIFO: what FIFO does with the EDF winner's fleet
    if "edf" in cheapest:
        n_edf = cheapest["edf"][0]
        rep = by_n.get(("fifo", n_edf))
        if rep is None:
            rep = summarize(simulate(wl, service, StaticPolicy(n_edf),
                                     discipline="fifo",
                                     initial_replicas=n_edf,
                                     max_replicas=QUOTA))
        summary["fifo_at_edf_capacity"] = _class_record(rep, n_edf)
    return summary, cheapest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_fleet.json",
                    help="JSON results path (CI uploads this artifact)")
    args = ap.parse_args()
    compile_cache.place()
    t0 = time.perf_counter()
    reports, records = run(full=args.full)
    tiered, cheapest = run_tiered(full=args.full)
    bench = {
        "benchmark": "fleet_scaling",
        "full": args.full,
        "quota_per_pool": QUOTA,
        "cold_start_s": COLD_START_S,
        "total_wall_clock_s": time.perf_counter() - t0,
        "records": records,
        "tiered_sla": tiered,
    }
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2)
    print(comparison_table(reports))
    print("\ncheapest fleet meeting >=99% SLO per trace "
          f"(quota {QUOTA} replicas/pool):")
    print(cost_efficiency_table(reports))
    print("\ntiered-SLA mixed-class sweep (cheapest feasible fleet per "
          "discipline, every class >= "
          f"{TIERED_ATTAINMENT_BAR * 100:.0f}%):")
    print(class_table([rep for _, rep in cheapest.values()]))
    if "fifo_at_edf_capacity" in tiered:
        eq = tiered["fifo_at_edf_capacity"]
        print(f"\nFIFO at the EDF winner's capacity ({eq['replicas']} "
              "replicas): worst class attainment "
              f"{eq['worst_class_attainment'] * 100:.1f}% "
              f"(bar {TIERED_ATTAINMENT_BAR * 100:.0f}%)")
    print(f"\nwrote {len(records)} records + tiered summary to {args.out}")


if __name__ == "__main__":
    main()
