"""The surveillance cell at a CPU size: a sound run is correct and carries
exactly the result keys; the timed path broken underneath (half of a batch
left out, SPRT's alarms lost), or the control in its place, comes out not
correct."""
import tinyroot

import json

import numpy as np
import pytest

CELL = "mset-surveil-b512"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench"))


def test_sound_run(root, capsys):
    line = tinyroot.run(root, CELL, capsys)
    assert list(line) == KEYS
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"obs_per_s", "batch_p95_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_run_keys(root, capsys):
    line = tinyroot.run(root, CELL, capsys, trace=1)
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]
    assert line["correct"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _half(estimate):
    """Half of the batch left out: the second half's estimate never made,
    its x_hat left at zero."""
    def broken(model, X, impl="auto"):
        h = X.shape[0] // 2
        x_hat, _ = estimate(model, X[:h], impl=impl)
        x_hat = np.concatenate([np.asarray(x_hat),
                                np.zeros((X.shape[0] - h, X.shape[1]),
                                         np.float32)])
        return x_hat, np.asarray(X) - x_hat
    return broken


def _silent(sprt):
    """An answer altered where it is produced: SPRT's alarms lost."""
    def broken(r, sigma, p, mu=None):
        alarms, sp, sn = sprt(r, sigma, p, mu=mu)
        return alarms & False, sp, sn
    return broken


@pytest.mark.parametrize("fault,target", [(_half, "estimate"),
                                          (_silent, "sprt")])
def test_broken_path_is_not_correct(root, capsys, monkeypatch, fault, target):
    from repro import mset

    monkeypatch.setattr(mset, target, fault(getattr(mset, target)))
    line = tinyroot.run(root, CELL, capsys)
    assert not line["correct"], line["checks"]


def test_control_is_not_correct(tmp_path):
    """The reference at three bfloat16 passes per product, in the program's
    place. It shows once the model is ill-conditioned enough: at 128 x 512
    it read 5.8 against the limit of 10, at 512 x 2048 17.7 (host runs)."""
    from benchlib import harness, spec

    root = tinyroot.make(tmp_path, dict(tinyroot.SMALL, **{
        "configs/mset2-1024x4096.json": {"n_signals": 512, "n_memvec": 2048,
                                         "n_train": 4096, "gamma": 34.0},
        "traffic/surveil-b512.json": {"batch": 256, "pool_batches": 4,
                                      "fault_start": 64, "check_batches": 2}}))
    cell = spec.Cell(root, CELL)
    passed, checks = harness.judge(
        cell.driver().control(cell.config, cell.traffic, 7), cell.limits)
    assert not passed, json.dumps(checks)
