"""Smoke run of the scoping path on TPU, through the entry points a user calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded MSET service on a 2x2 host

On one chip, four phases run in this one process:

1. device gate: the first device must be a TPU. There is no CPU fallback.
2. MSET2 at ``mset_scenario``'s width (1024 signals x 4096 memory vectors,
   surveillance batches of 512) with the Pallas similarity kernel passed
   explicitly (``impl="pallas"``). Checks: the kernel's 4096 x 512 block
   against the reference at highest matmul precision; a ramp fault injected
   on one signal makes SPRT alarm on it, with the host reference's verdict
   and false-alarm rate on the other signals; the residual RMS beside a
   float64 host evaluation of the same model; and ``x_hat`` of a
   well-conditioned model of the same data against its float64 evaluation.
3. Measured scoping (``ContainerStress.run_measured``) of the
   ``examples/scope_containers.py`` workload. Every cell must yield a row.
4. The ``tune_controller`` flash-crowd racing round on the compiled fleet
   simulator (``backend="jax"``), coarse and at 4 preemptive substeps, each
   against the numpy reference. The winner must be the same.

``--chips 4`` runs only the sharded surveillance service
(``mset.service.make_service`` on the catalog's v5e-4 mesh) against the same
model's single-device ``estimate(impl="ref")`` and the same float64 checks.

Lines before the last are smoke findings, not benchmark numbers. The last line
is one JSON object naming the device; when a phase fails, or JAX finds no TPU,
the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
for _sub in ("src", "benchmarks", "examples"):
    sys.path.insert(0, os.path.join(ROOT, _sub))

import jax
import jax.numpy as jnp
import numpy as np

import sim_perf
from repro import compile_cache
from repro.core import ContainerStress, get_shape
from repro.fleet import Objective, PredictivePolicy, evaluate_candidates, telemetry
from repro.kernels.similarity import similarity
from repro.mset import SPRTParams, estimate, sprt, train
from repro.mset.service import make_service
from repro.tpss import TPSSParams, inject_anomaly, synthesize
from scope_containers import mset_workload

SEED = 0
N_SIGNALS, N_MEMVEC, BATCH = 1024, 4096, 512    # mset_scenario's width and batch
N_TRAIN = 8192                                  # training observations
SERVICE_BATCH = 2048                            # --chips 4 surveillance batch
# the injected incipient fault: a ramp on one signal from FAULT_START on that
# reaches FAULT_HEIGHT of the signal's standard deviations at the window's
# end, whatever the batch (a fixed slope grows with the window, and a ramp of
# ~100 deviations moves every signal's estimate through the similarity)
FAULT_SIGNAL, FAULT_START, FAULT_HEIGHT = 3, 128, 19.2
# Limits on the surveillance readings; each sits between the readings of a
# sound estimate and of a broken one (PERF.md section 2 lists both).
# Device residual RMS within this factor of the float64 evaluation's:
RMS_FACTOR = 2.0
# SPRT's per-observation false-alarm rate on the signals without a fault
# within this factor of the reference's. The rate is compared, not the count
# of signals that alarm: TPSS residuals are serially correlated, so over a
# window of thousands of observations most signals alarm at least once.
FA_FACTOR = 2.0
# At reg 1e-6 float32 rounding in x_hat is of the order of the residual
# itself (Ginv's eigenvalue inverses reach 1e6), so x_hat is checked on a
# model of the same data at CONDITIONED_REG: rms(x_hat - float64) at most
# XHAT_TOL of the float64 residual RMS.
CONDITIONED_REG, XHAT_TOL = 1e-2, 1e-2
# kernel vs reference: one bf16 MXU pass over the 1024-signal contraction
# moves a similarity by at most ~2.1e-4 here (emulated on the host); a tiling
# or layout fault moves values by 1e-1 or more
SIM_TOL = 1e-3
SCOPING_GRID = {"n_signals": [64, 1024], "n_memvec": [2048, 4096]}
SCOPING_TRAIN = 4096                            # observations per scoping cell
SCOPING_REPS = 2
# SimResult outputs in the order one bin computes them
SIM_FIELDS = ("admitted", "dropped", "served", "queue", "utilization",
              "replicas", "billed_replicas", "latency_s", "ok_served")


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def device_gate(n_chips: int) -> dict:
    """The device as JAX reports it; exits when it is not ``n_chips`` TPUs."""
    devs = jax.devices()
    d = devs[0]
    found = f"{len(devs)} x {d.platform} ({d.device_kind})"
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {found}")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, JAX found {found}")
    say(f"device: {found}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ------------------------------- MSET2 --------------------------------------

def fit_mset(n_signals, n_memvec, n_train, batch, impl):
    """Seeded TPSS telemetry; MSET2 trained on the first ``n_train``
    observations, at the default ``reg`` and at ``CONDITIONED_REG``. Returns
    both models, a clean window of ``batch`` observations and a following
    window with the ramp fault injected."""
    X = synthesize(jax.random.PRNGKey(SEED),
                   TPSSParams(n_signals=n_signals, n_obs=n_train + 2 * batch))
    model = train(X[:n_train], n_memvec, impl=impl)
    conditioned = train(X[:n_train], n_memvec, reg=CONDITIONED_REG, impl=impl)
    slope = (FAULT_HEIGHT * float(model.std[FAULT_SIGNAL])
             / (batch - FAULT_START))
    faulty = inject_anomaly(X[n_train + batch:], FAULT_START, FAULT_SIGNAL,
                            slope)
    return model, conditioned, X[n_train:n_train + batch], faulty


def surveil(estimate_fn, clean, faulty):
    """SPRT on the faulty window's residuals, calibrated on the clean
    window's. Returns (x_hat, residuals, alarms) as numpy arrays."""
    _, r_clean = estimate_fn(clean)
    x_hat, r = estimate_fn(faulty)
    alarms, _, _ = sprt(r, jnp.std(r_clean, axis=0), SPRTParams(),
                        mu=jnp.mean(r_clean, axis=0))
    return np.asarray(x_hat), np.asarray(r), np.asarray(alarms)


def verdict(alarms) -> dict:
    """Whether SPRT caught the injected fault and when; on the other
    signals, the share of observations that alarmed and how many signals
    alarmed anywhere in the window."""
    after = alarms[FAULT_START:, FAULT_SIGNAL]
    others = np.delete(alarms, FAULT_SIGNAL, axis=1)
    return {"fault_alarm": bool(after.any()),
            "first_alarm": int(FAULT_START + np.argmax(after))
            if after.any() else None,
            "false_alarm_rate": float(others.mean()),
            "other_signals_alarmed": int(others.any(axis=0).sum())}


def check_verdicts(v, v_ref, what: str) -> None:
    """The same SPRT verdict as the reference: both alarm on the injected
    fault, and the false-alarm rates agree within ``FA_FACTOR``."""
    check(v["fault_alarm"] and v_ref["fault_alarm"],
          f"SPRT missed the injected fault ({what} {v}, reference {v_ref})")
    fa, fa_ref = v["false_alarm_rate"], v_ref["false_alarm_rate"]
    check(fa <= FA_FACTOR * fa_ref and fa_ref <= FA_FACTOR * fa,
          f"{what} false-alarm rate {fa:.6g} against the reference's "
          f"{fa_ref:.6g}")


def estimate_f64(model, X):
    """The model's estimate in float64 numpy, from the same D and Ginv."""
    D, G, mu, sd = (np.asarray(a, np.float64)
                    for a in (model.D, model.Ginv, model.mean, model.std))
    Xs = (np.asarray(X, np.float64) - mu) / sd
    d2 = np.maximum((D * D).sum(1)[:, None] + (Xs * Xs).sum(1)[None, :]
                    - 2.0 * D @ Xs.T, 0.0)
    if model.kind == "inverse_distance":
        K = 1.0 / (1.0 + np.sqrt(d2) / model.gamma)
    else:
        K = np.exp(-d2 / (2.0 * model.gamma ** 2))
    return ((G @ K).T @ D) * sd + mu


def rms(a) -> float:
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def check_float64(estimate_fn, model, conditioned, X, what: str) -> None:
    """``estimate_fn(model)`` against float64 evaluations on ``X``: the
    residual RMS of ``model`` within ``RMS_FACTOR``, and ``x_hat`` of the
    well-conditioned model within ``XHAT_TOL``."""
    X64 = np.asarray(X, np.float64)
    x_hat, r = (np.asarray(a) for a in estimate_fn(model)(X))
    x64 = estimate_f64(model, X)
    ratio = rms(r) / rms(X64 - x64)
    say(f"{what}: residual RMS {rms(r):.6g} against {rms(X64 - x64):.6g} for "
        f"float64 of the same D/Ginv (ratio {ratio:.6g}, limit {RMS_FACTOR:g});"
        f" max |x_hat - float64| {float(np.max(np.abs(x_hat - x64))):.6g}")
    check(np.isfinite(r).all(), f"non-finite residuals from the {what}")
    check(1.0 / RMS_FACTOR <= ratio <= RMS_FACTOR,
          f"{what} residual RMS is off the float64 evaluation's by {ratio:.6g}x")
    xc = np.asarray(estimate_fn(conditioned)(X)[0])
    xc64 = estimate_f64(conditioned, X)
    err = rms(xc - xc64) / rms(X64 - xc64)
    say(f"{what}, model at reg {CONDITIONED_REG:g}: rms(x_hat - float64) "
        f"{err:.6g} of the float64 residual RMS (limit {XHAT_TOL:g})")
    check(err <= XHAT_TOL, f"{what} x_hat is off float64 by {err:.6g}")


def phase_mset(n_signals=N_SIGNALS, n_memvec=N_MEMVEC, n_train=N_TRAIN,
               batch=BATCH, impl="pallas"):
    model, conditioned, clean, faulty = fit_mset(n_signals, n_memvec,
                                                 n_train, batch, impl)
    say(f"MSET2 trained: {n_signals} signals x {n_memvec} memory vectors "
        f"from {n_train} observations, gamma {model.gamma:.6g} "
        f"(similarity impl={impl!r})")

    Xs = (faulty - model.mean) / model.std
    K = similarity(model.D, Xs, gamma=model.gamma, kind=model.kind, impl=impl)
    with jax.default_matmul_precision("highest"):
        K_ref = similarity(model.D, Xs, gamma=model.gamma, kind=model.kind,
                           impl="ref")
    err = float(jnp.max(jnp.abs(K - K_ref)))
    say(f"similarity block {tuple(K.shape)}: max |kernel - reference at "
        f"highest precision| = {err:.3e} (tolerance {SIM_TOL:g})")
    check(K.shape == (n_memvec, batch) and err <= SIM_TOL,
          f"similarity kernel disagrees with the reference: {err:.3e}")

    _, _, alarms = surveil(lambda X: estimate(model, X, impl=impl),
                           clean, faulty)
    cpu = jax.devices("cpu")[0]
    host_model, host_clean, host_faulty = jax.device_put(
        (model, clean, faulty), cpu)
    _, r_host, alarms_host = surveil(
        lambda X: estimate(host_model, X, impl="ref"), host_clean, host_faulty)
    v, v_host = verdict(alarms), verdict(alarms_host)
    say(f"SPRT on signal {FAULT_SIGNAL} (ramp from observation {FAULT_START}"
        f" to {FAULT_HEIGHT:g} sd): device {v}; host reference (impl='ref') "
        f"{v_host}; host float32 residual RMS {rms(r_host):.6g}")
    check_verdicts(v, v_host, "device")
    check_float64(lambda mod: lambda X: estimate(mod, X, impl=impl),
                  model, conditioned, faulty, "device estimate")


# --------------------------- measured scoping -------------------------------

def phase_scoping(grid=SCOPING_GRID, n_train=SCOPING_TRAIN, n_surveil=BATCH,
                  impl="pallas", reps=SCOPING_REPS):
    res = ContainerStress().run_measured(
        lambda p: mset_workload(p, n_train=n_train, n_surveil=n_surveil,
                                impl=impl),
        grid, reps=reps, verbose=True)
    say(f"scoping: {len(res.rows)} rows, each the mean of {reps} timed "
        f"train+surveil runs ({n_train} training + {n_surveil} surveilled "
        f"observations)")
    want = [dict(zip(grid, v)) for v in itertools.product(*grid.values())]
    missing = [p for p in want if p not in [row.params for row in res.rows]]
    check(not missing, f"scoping cells without a row: {missing}")
    check(all(math.isfinite(row.mean_s) for row in res.rows),
          "a scoping row has no finite time")


# ----------------------------- racing rounds --------------------------------

def first_divergence(ts, params, n_seeds) -> str:
    """The first SimResult output, bin by bin, in which the two backends
    differ for one candidate."""
    a = ts["numpy"].simulate(params, 0, n_seeds, backend="numpy")
    b = ts["jax"].simulate(params, 0, n_seeds, backend="jax")
    first = None
    for f in SIM_FIELDS:
        x, y = np.asarray(getattr(a, f), float), np.asarray(getattr(b, f), float)
        bins = np.nonzero((x != y).reshape(x.shape[0], x.shape[1], -1)
                          .any(axis=(0, 2)))[0]
        if bins.size and (first is None or bins[0] < first[1]):
            first = (f, int(bins[0]))
    if first is None:
        return "no per-bin output differs; only the host-side scores do"
    f, t = first
    x, y = getattr(a, f)[:, t], getattr(b, f)[:, t]
    s = int(np.argmax(np.abs(np.asarray(x, float) - np.asarray(y, float))
                      .reshape(len(x), -1).max(axis=1)))
    return (f"first differing output: {f} at bin {t} (seed {s}): numpy "
            f"{x[s]!r} vs jax {y[s]!r}")


def phase_racing(cell, n_substeps=1, preemptive=False):
    n_cands, n_seeds, duration_s = cell
    ts = {b: sim_perf.build_scenario(n_seeds, duration_s, b, n_substeps,
                                     preemptive)
          for b in ("jax", "numpy")}
    objective = Objective(min_attainment=1.0, penalty_usd_per_hour=1e5)
    cands = PredictivePolicy.param_space().sample_lhs(n_cands,
                                                      seed=sim_perf.SEED)
    with telemetry.session() as tel:
        evaluate_candidates(ts["jax"], cands, objective, backend="jax")
        ev_jax = evaluate_candidates(ts["jax"], cands, objective,
                                     backend="jax")
    disp = tel.metrics.snapshot()["counter"].get("jaxsim_dispatch_total", {})
    ev_np = evaluate_candidates(ts["numpy"], cands, objective,
                                backend="numpy")
    deltas = [float(np.abs(a.score - b.score).max())
              for a, b in zip(ev_np, ev_jax)]
    win_jax = min(range(n_cands), key=lambda i: ev_jax[i].mean_score())
    win_np = min(range(n_cands), key=lambda i: ev_np[i].mean_score())
    say(f"racing round {n_cands} candidates x {n_seeds} seeds x "
        f"{ts['jax'].workload.n_bins} bins, n_substeps={n_substeps}, "
        f"preemptive={preemptive}: winner jax #{win_jax}, numpy #{win_np}; "
        f"max |score jax - numpy| {max(deltas)!r}; dispatches: "
        f"{disp.get('kind=cold', 0):g} cold, {disp.get('kind=warm', 0):g} warm")
    if max(deltas) != 0.0:
        worst = int(np.argmax(deltas))
        say(f"candidate #{worst}: "
            + first_divergence(ts, cands[worst], n_seeds))
    check(win_jax == win_np, "jax and numpy pick different winners")


# ------------------------- sharded service (4 chips) ------------------------

def phase_service(n_signals=N_SIGNALS, n_memvec=N_MEMVEC, n_train=N_TRAIN,
                  batch=SERVICE_BATCH, impl="pallas"):
    mesh = get_shape("v5e-4").make_mesh()
    model, conditioned, clean, faulty = fit_mset(n_signals, n_memvec,
                                                 n_train, batch, impl)
    x_svc, _, alarms_svc = surveil(make_service(model, mesh), clean, faulty)
    x_ref, _, alarms_ref = surveil(
        lambda X: estimate(model, X, impl="ref"), clean, faulty)
    v_svc, v_ref = verdict(alarms_svc), verdict(alarms_ref)
    say(f"sharded service on v5e-4 mesh {dict(mesh.shape)} "
        f"({n_signals} x {n_memvec}, batch {batch}): SPRT {v_svc}; "
        f"single-device estimate(impl='ref') {v_ref}; max |x_hat service - "
        f"single device| {float(np.max(np.abs(x_svc - x_ref))):.6g}")
    check_verdicts(v_svc, v_ref, "service")
    check_float64(lambda mod: make_service(mod, mesh), model, conditioned,
                  faulty, "sharded service")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded MSET service on a 2x2 host")
    args = ap.parse_args(argv)
    device = device_gate(args.chips)
    compile_cache.place()
    if args.chips == 4:
        phases = [("sharded MSET service", phase_service)]
    else:
        phases = [
            ("MSET2", phase_mset),
            ("measured scoping", phase_scoping),
            ("racing round", lambda: phase_racing(sim_perf.HEADLINE)),
            ("racing round, substeps", lambda: phase_racing(
                sim_perf.SUBSTEP_CELL, sim_perf.N_SUBSTEPS, True)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:      # report every phase, then fail the run
            traceback.print_exc()
            say(f"phase {name}: FAILED ({e})")
            failed.append(name)
            continue
        say(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s wall)")
    if failed:
        sys.exit(f"chip_smoke: failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
