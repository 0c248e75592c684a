"""The paper's headline workflow: autonomously scope a cloud container for a
customer's ML use case, from tiny (customer A) to fleet-scale (customer B).

Nested-loop Monte Carlo scoping (measured on this box) -> response surface ->
extrapolated cost for each catalog TPU shape (analytic roofline) -> cheapest
feasible shape + elasticity growth plan.

    PYTHONPATH=src python examples/scope_containers.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks/

import jax

from benchmarks.common import mset_surveil_flops_bytes, tpu_roofline_time
from repro.core import (CATALOG, CellResult, Constraint, ContainerStress,
                        RooflineTerms, fit_response_surface, grid_to_matrix,
                        recommend, render_ascii_surface)
from repro.configs.mset_paper import CUSTOMER_A, CUSTOMER_B
from repro.mset import estimate, train
from repro.tpss import TPSSParams, synthesize


def mset_workload(params, n_train: int = 1536, n_surveil: int = 512,
                  impl: str = "auto"):
    """One scoping cell: TPSS telemetry for ``params["n_signals"]`` signals,
    MSET2 trained on the first ``n_train`` observations with
    ``params["n_memvec"]`` memory vectors, then the next ``n_surveil``
    surveilled. Returns the zero-arg unit of work ``run_measured`` times;
    it includes ``train``'s eigendecomposition, which runs on the host."""
    key = jax.random.PRNGKey(params["n_signals"] * 7 + params["n_memvec"])
    X = synthesize(key, TPSSParams(n_signals=params["n_signals"],
                                   n_obs=n_train + n_surveil))

    def run():
        m = train(X[:n_train], n_memvec=params["n_memvec"], impl=impl)
        return estimate(m, X[n_train:], impl=impl)[1]
    return run


def measured_scoping():
    print("=== 1. nested-loop Monte Carlo scoping (measured, this box) ===")
    cs = ContainerStress()
    res = cs.run_measured(
        mset_workload,
        {"n_signals": [8, 16, 32, 64], "n_memvec": [64, 128, 256, 512]},
        reps=2, constraint=lambda p: p["n_memvec"] >= 2 * p["n_signals"],
        verbose=False)
    names, X, y = res.to_arrays()
    surf = fit_response_surface(names, X, y)
    print(f"fitted response surface over (n_signals, n_memvec): r^2={surf.r2:.3f}")
    xs, ys, Z = grid_to_matrix(res.rows, "n_memvec", "n_signals")
    print(render_ascii_surface(xs, ys, Z, "n_memvec", "n_signals",
                               "measured train+surveil cost ('·' = infeasible)"))
    return surf


def analytic_recommendation(use_case, sample_rate_hz: float, fleet: int = 1,
                            window_s: float = 60.0):
    """Roofline cost of the MSET surveillance service on each catalog shape.

    fleet assets, each with its own (D, Ginv) model; one surveillance window of
    `window_s` seconds of observations per asset must finish within the window
    (real-time constraint) and all models must fit aggregate HBM.
    """
    print(f"\n=== scoping '{use_case.name}': {use_case.n_signals} signals x "
          f"{fleet} assets, memvec={use_case.n_memvec} @ {sample_rate_hz} Hz ===")
    rows = []
    n_obs = max(int(sample_rate_hz * window_s), 1)
    model_bytes = 4.0 * (use_case.n_memvec**2
                         + 2 * use_case.n_memvec * use_case.n_signals)
    for shape in CATALOG:
        f, b = mset_surveil_flops_bytes(use_case.n_signals, use_case.n_memvec, n_obs)
        f, b = f * fleet, b * fleet
        t = tpu_roofline_time(f, b, chips=shape.chips)
        rows.append(CellResult(params={"chips": shape.chips}, shape_name=shape.name,
                               terms=RooflineTerms(t, t * 0.8, 0.0),
                               analysis={"peak_memory_per_device":
                                         fleet * model_bytes / shape.chips}))
    cons = Constraint(max_step_latency_s=window_s)
    rec = recommend(rows, cons)
    for name, t, price, ok in rec.ranking:
        print(f"  {name:12s} t_window={t*1e3:10.2f}ms  ${price:8.2f}/hr  "
              f"{'OK' if ok else 'infeasible (latency or HBM)'}")
    print(f"--> {rec.shape.name if rec.shape else 'NO SHAPE'} ({rec.reason})")
    return rec


def main():
    measured_scoping()
    # Customer A: 20 signals @ 1/hr (paper §I) — anything works; cheapest wins.
    analytic_recommendation(CUSTOMER_A, sample_rate_hz=1 / 3600)
    # Customer B: fleet of 200 Airbus A320s, 75k sensors @ 1 Hz each — per-plane
    # MSET models must fit aggregate HBM; scoping finds the smallest slice.
    analytic_recommendation(CUSTOMER_B, sample_rate_hz=1.0, fleet=200)


if __name__ == "__main__":
    main()
