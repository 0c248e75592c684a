"""Closed-loop MSET2 surveillance: one batch of observations in flight.

A pool of ``pool_batches`` batches of ``batch`` x ``n_signals`` observations
is made from the seed and staged in host memory; every ``fault_every``-th
batch carries a ramp fault on one signal drawn from the seed. Each call of the
window takes the next batch of the pool to the device, runs the program's
``repro.mset.estimate`` (the Pallas similarity kernel on a TPU) and
``repro.mset.sprt`` (calibrated in set-up on a clean batch), and brings the
alarms back to the host.

The output check: the x_hat, residuals and alarms that the window itself
produced for ``check_batches`` pool batches drawn from the seed, against the
float32 reference trained on the same telemetry (``reference/mset_ref.py``).
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from reference import mset_ref, tpss


def make_data(config: dict, traffic: dict, seed: int) -> dict:
    """The cell's telemetry from the seed: the training window on the device,
    a clean calibration batch, the pool of batches in host memory, and which
    batches carry which fault."""
    import jax.numpy as jnp

    n, n_train = config["n_signals"], config["n_train"]
    b, P = traffic["batch"], traffic["pool_batches"]
    X = tpss.synthesize(tpss.seed_key(seed), n, n_train + b * (1 + P),
                        **config.get("tpss", {}))
    rng = np.random.default_rng(seed)
    train_sd = np.asarray(jnp.std(X[:n_train], axis=0))
    start, height = traffic["fault_start"], traffic["fault_height_sd"]
    faults, pool = {}, []
    for i in range(P):
        x = X[n_train + b * (1 + i):n_train + b * (2 + i)]
        if i % traffic["fault_every"] == 0:
            sig = int(rng.integers(n))
            faults[i] = sig
            x = tpss.inject_anomaly(x, start, sig,
                                    height * float(train_sd[sig]) / (b - start))
        pool.append(np.asarray(x))
    faulty, clean = sorted(faults), [i for i in range(P) if i not in faults]
    k = traffic["check_batches"]
    sample = sorted(rng.choice(faulty, k // 2, replace=False).tolist()
                    + rng.choice(clean, k - k // 2, replace=False).tolist())
    return {"train": X[:n_train], "calib": X[n_train:n_train + b],
            "pool": np.stack(pool), "faults": faults, "sample": sample}


def reference(config: dict, data: dict, ar=None) -> dict:
    """The model of the reference arithmetic ``ar`` (``Plain`` by default),
    trained on the same telemetry, with its SPRT calibration."""
    ar = ar or mset_ref.Plain()
    model = mset_ref.train(ar, np.asarray(data["train"]), config["n_memvec"],
                           config["reg"], config["gamma"])
    _, r = mset_ref.estimate(ar, model, np.asarray(data["calib"]))
    return {"ar": ar, "model": model, "sigma": r.std(axis=0),
            "mu": r.mean(axis=0)}


def answers_of(config: dict, ref: dict, data: dict) -> dict:
    """{pool index: (x_hat, residuals, alarms)} of the reference ``ref`` on
    the sampled batches."""
    out = {}
    for i in data["sample"]:
        x_hat, r = mset_ref.estimate(ref["ar"], ref["model"], data["pool"][i])
        out[i] = (x_hat, r, mset_ref.sprt(r, ref["sigma"], ref["mu"],
                                          **config["sprt"]))
    return out


def _rms(a) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(a, np.float64)))))


def compare(got: dict, want: dict, data: dict, fault_start: int) -> list:
    """The numbers compared:

    * ``resid_rms_ratio``: the worst batch's residual RMS over the
      reference's, or its inverse, whichever is larger (1 at best);
    * ``xhat_gap``: the worst batch's RMS gap of x_hat over the reference's
      residual RMS (0 at best);
    * ``fault_missed``: faulty batches whose fault the reference's SPRT
      caught after it began and this one did not (exact: 0).
    """
    ratio, gap, missed = 1.0, 0.0, 0
    for i, (x_hat, r, alarms) in sorted(got.items()):
        wx, wr, wa = want[i]
        q = _rms(r) / _rms(wr)
        ratio = max(ratio, q, 1.0 / q)
        gap = max(gap, _rms(np.asarray(x_hat, np.float64) - wx) / _rms(wr))
        if i in data["faults"]:
            sig = data["faults"][i]
            caught = bool(np.asarray(alarms)[fault_start:, sig].any())
            missed += int(wa[fault_start:, sig].any() and not caught)
    return [("resid_rms_ratio", ratio), ("xhat_gap", gap),
            ("fault_missed", missed)]


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax.numpy as jnp
        from repro import mset

        self.config, self.traffic = config, traffic
        self._mset = mset
        self.data = make_data(config, traffic, seed)
        self.model = mset.train(self.data["train"], config["n_memvec"],
                                kind=config["kind"], gamma=config["gamma"],
                                reg=config["reg"])
        _, r = mset.estimate(self.model, self.data["calib"])
        self.sigma, self.mu = jnp.std(r, axis=0), jnp.mean(r, axis=0)
        self.params = mset.SPRTParams(**config["sprt"])
        self.kept = {}
        self.attempted = self.failed = 0
        for i in range(2):          # warm every shape of the window
            self._step(i)

    def _step(self, i: int):
        x = self.data["pool"][i % len(self.data["pool"])]
        with TraceAnnotation("bench.h2d"):
            xd = jax.device_put(x)
        with TraceAnnotation("bench.estimate"):
            x_hat, r = self._mset.estimate(self.model, xd)
        with TraceAnnotation("bench.sprt"):
            alarms, _, _ = self._mset.sprt(r, self.sigma, self.params,
                                           mu=self.mu)
        with TraceAnnotation("bench.d2h"):
            return x_hat, r, np.asarray(alarms)

    def run(self, seconds: float) -> None:
        """One window: batches until ``seconds`` have passed and every
        sampled batch of the pool has been through it."""
        sample = set(self.data["sample"])
        last = max(sample)
        latencies, failed = [], self.failed
        t_start = time.perf_counter()
        i = 0
        while True:
            t0 = time.perf_counter()
            try:
                x_hat, r, alarms = self._step(i)
            except Exception:
                self.failed += 1
                if self.failed > 10:
                    raise
            else:
                if i in sample:
                    self.kept[i] = (x_hat, r, alarms)
            t1 = time.perf_counter()
            latencies.append(t1 - t0)
            self.attempted += 1
            i += 1
            if t1 - t_start >= seconds and i > last:
                break
        self.window = {"calls": i, "done": i - (self.failed - failed),
                       "elapsed_s": t1 - t_start, "latencies": latencies}

    def end_to_end(self) -> dict:
        """Of the last window: observations whose alarms came back, per
        second, and the 95th percentile of every batch's latency."""
        w = self.window
        return {"obs_per_s": w["done"] * self.traffic["batch"] / w["elapsed_s"],
                "batch_p95_ms": 1e3 * float(np.percentile(w["latencies"], 95))}

    def layer(self) -> dict:
        """The shapes, and the batches and seconds of the last window."""
        c, w = self.config, self.window
        return {"m": c["n_memvec"], "n": c["n_signals"],
                "b": self.traffic["batch"], "calls": w["calls"],
                "elapsed_s": w["elapsed_s"]}

    def release(self) -> None:
        self.kept = {i: tuple(np.asarray(a) for a in v)
                     for i, v in self.kept.items()}
        self.data["train"] = np.asarray(self.data["train"])
        self.data["calib"] = np.asarray(self.data["calib"])
        del self.model, self.sigma, self.mu

    def check(self) -> list:
        ref = reference(self.config, self.data)
        want = answers_of(self.config, ref, self.data)
        missing = [i for i in self.data["sample"] if i not in self.kept]
        got = dict(self.kept)
        for i in missing:       # never produced in the window: a wrong answer
            got[i] = tuple(np.full_like(w, np.nan, dtype=np.float64)
                           if w.dtype != bool else ~w for w in want[i])
        return compare(got, want, self.data, self.traffic["fault_start"])


def control(config: dict, traffic: dict, seed: int) -> list:
    """The numbers compared, with the reference in float32 at three bfloat16
    passes per product (``mset_ref.Control``) put in the program's place."""
    data = make_data(config, traffic, seed)
    data.update(train=np.asarray(data["train"]), calib=np.asarray(data["calib"]))
    want = answers_of(config, reference(config, data), data)
    got = answers_of(config, reference(config, data, mset_ref.Control()), data)
    return compare(got, want, data, traffic["fault_start"])
