"""SPRT detector: false-alarm bound + detection latency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.mset import SPRTParams, empirical_false_alarm_rate, sprt
from repro.mset.sprt import SPRT_BLOCK


def test_false_alarm_rate_on_clean_noise():
    key = jax.random.PRNGKey(1)
    r = jax.random.normal(key, (20_000, 8))
    alarms, _, _ = sprt(r, jnp.ones(8), SPRTParams(alpha=1e-3, beta=1e-3, m_shift=4.0))
    far = float(empirical_false_alarm_rate(alarms))
    assert far < 5e-3, far


def test_detects_mean_shift_quickly():
    key = jax.random.PRNGKey(2)
    r = jax.random.normal(key, (2000, 4))
    r = r.at[1000:, 2].add(3.0)  # 3-sigma shift on signal 2
    alarms, _, _ = sprt(r, jnp.ones(4), SPRTParams(m_shift=3.0))
    a = np.asarray(alarms)
    post = np.argwhere(a[1000:, 2]).ravel()
    assert len(post) > 0 and post[0] < 50, post[:3]
    # other signals stay mostly quiet
    assert a[:, [0, 1, 3]].mean() < 0.01


def test_detects_negative_shift():
    key = jax.random.PRNGKey(3)
    r = jax.random.normal(key, (1000, 2))
    r = r.at[500:, 0].add(-3.0)
    alarms, _, _ = sprt(r, jnp.ones(2))
    post = np.argwhere(np.asarray(alarms)[500:, 0]).ravel()
    assert len(post) > 0 and post[0] < 50


# ---------------- one compiled program: thresholds, recursion, lowering ------

@pytest.mark.parametrize("p, upper, lower", [
    (SPRTParams(), 6.906754970550537, -6.906754970550537),
    (SPRTParams(alpha=1e-4, beta=1e-4), 9.210240364074707, -9.210240364074707),
    (SPRTParams(alpha=0.05, beta=0.1), 2.890371799468994, -2.2512917518615723),
])
def test_thresholds_are_float32_logs_taken_on_the_host(p, upper, lower):
    with jax.transfer_guard("disallow"):      # no device value on the way
        hi, lo = p.upper, p.lower
    assert type(hi) is float and type(lo) is float
    assert (hi, lo) == (upper, lower)


def _ramp_residuals(T=512, n=64, sig=5, height=4.0):
    start = T // 2
    rng = np.random.default_rng(14)
    sigma = rng.uniform(0.5, 2.0, n).astype(np.float32)
    mu = rng.normal(0.0, 0.3, n).astype(np.float32)
    r = (mu + sigma * rng.standard_normal((T, n))).astype(np.float32)
    r[start:, sig] += height * sigma[sig] * np.linspace(0, 1, T - start,
                                                        dtype=np.float32)
    return r, sigma, mu


def _sprt_loop(r, sigma, mu, p):
    """The restarted two-sided recursion, one step at a time in float32."""
    f = np.float32
    z = (r.astype(f) - mu.astype(f)) / sigma.astype(f)
    M = p.m_shift
    inc_pos, inc_neg = M * z - f(0.5 * M * M), -M * z - f(0.5 * M * M)
    hi, lo = f(p.upper), f(p.lower)
    sp, sn = np.zeros(r.shape[1], f), np.zeros(r.shape[1], f)
    alarms = np.zeros(r.shape, bool)
    sps, sns = np.zeros(r.shape, f), np.zeros(r.shape, f)
    for t in range(r.shape[0]):
        sp = np.maximum(sp + inc_pos[t], lo)
        sn = np.maximum(sn + inc_neg[t], lo)
        alarms[t] = (sp >= hi) | (sn >= hi)
        sp = np.where(sp >= hi, f(0.0), sp)
        sn = np.where(sn >= hi, f(0.0), sn)
        sps[t], sns[t] = sp, sn
    return alarms, sps, sns


@pytest.mark.parametrize("T", [512, 509, SPRT_BLOCK - 1, 3 * SPRT_BLOCK + 1])
def test_compiled_sprt_equals_the_float32_loop_on_a_ramp(T):
    """Whole blocks, a tail after them, a tail alone: every T is exact."""
    r, sigma, mu = _ramp_residuals(T=T)
    p = SPRTParams()
    alarms, sp, sn = sprt(jnp.asarray(r), jnp.asarray(sigma), p,
                          mu=jnp.asarray(mu))
    want_a, want_sp, want_sn = _sprt_loop(r, sigma, mu, p)
    assert np.asarray(alarms)[T // 2:, 5].any()       # the ramp is caught
    np.testing.assert_array_equal(np.asarray(alarms), want_a)
    # the compiler may fuse M*z - M*M/2 into one multiply-add (XLA:CPU does),
    # one rounding fewer per increment; the state sums a few such differences
    # between restarts, each below an ulp of the threshold
    atol = 32 * np.finfo(np.float32).eps * p.upper
    np.testing.assert_allclose(np.asarray(sp), want_sp, rtol=0, atol=atol)
    np.testing.assert_allclose(np.asarray(sn), want_sn, rtol=0, atol=atol)


def test_the_compiled_loop_runs_a_block_of_rows_an_iteration():
    import re

    from repro.mset.sprt import _sprt_jit

    r = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    v = jax.ShapeDtypeStruct((64,), jnp.float32)
    hlo = _sprt_jit.lower(r, v, v, p=SPRTParams()).compile().as_text()
    trips = re.findall(r'known_trip_count":\{"n":"(\d+)"', hlo)
    assert trips == [str(512 // SPRT_BLOCK)]


@pytest.mark.parametrize("T", [512, 509])
def test_rows_counter_splits_blocks_and_tail(T):
    from repro import telemetry

    r, sigma, mu = (jnp.asarray(a) for a in _ramp_residuals(T=T, n=8))
    with telemetry.session() as tel:
        sprt(r, sigma, mu=mu)
        sprt(r, sigma, mu=mu)
    rows = tel.metrics.snapshot()["counter"]["mset_sprt_rows_total"]
    tail = T % SPRT_BLOCK
    assert (T == 509) == (tail > 0)                   # 509 leaves a tail
    assert rows == {"part=block": 2.0 * (T - tail), "part=tail": 2.0 * tail}


def test_warm_calls_lower_nothing():
    from repro import telemetry

    r, sigma, mu = (jnp.asarray(a) for a in _ramp_residuals(T=96, n=24))
    sprt(r, sigma, mu=mu)                             # the one lowering
    with telemetry.session() as tel:
        for _ in range(5):
            sprt(r, sigma, mu=mu)
    lowers = tel.metrics.snapshot()["counter"].get("jax_compile_events_total",
                                                   {})
    assert lowers.get("phase=lower", 0.0) == 0.0
    spans = tel.tracer.roots
    assert [s.name for s in spans] == ["mset.sprt"] * 5
    assert all(not s.children for s in spans)


@pytest.mark.parametrize("with_mu", [True, False])
def test_sprt_inside_jit_and_without_mu(with_mu):
    r, sigma, mu = (jnp.asarray(a) for a in _ramp_residuals(T=128, n=16))
    mu = mu if with_mu else None
    p = SPRTParams(m_shift=4.0)
    outer = jax.jit(lambda r, s, m: sprt(r * 1.0, s, p, mu=m))
    got = outer(r, sigma, mu)
    want = sprt(r, sigma, p, mu=mu)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if not with_mu:
        zero = sprt(r, sigma, p, mu=jnp.zeros_like(sigma))
        np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(zero[0]))


def test_the_sprt_ms_reader_still_finds_the_compiled_loop(monkeypatch):
    """``sprt_ms`` finds the SPRT by its while loop carrying the (b, n)
    alarms; the compiled program at (512, 64) still has that loop."""
    import importlib.util
    import re
    from pathlib import Path

    from repro.mset.sprt import _sprt_jit

    bench = Path(__file__).resolve().parents[1] / "bench"
    monkeypatch.syspath_prepend(str(bench))
    spec = importlib.util.spec_from_file_location(
        "bench_readers", bench / "benchlib" / "readers.py")
    readers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readers)
    r = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    v = jax.ShapeDtypeStruct((64,), jnp.float32)
    hlo = _sprt_jit.lower(r, v, v, p=SPRTParams()).compile().as_text()
    assert re.search(readers.sprt_scan(512, 64), hlo)
    assert not re.search(readers.sprt_scan(512, 65), hlo)
