"""Seeded synthetic sensor telemetry (TPSS), the generator of the MSET cells.

A copy of ``repro.tpss.synthesize`` and ``inject_anomaly`` as they stood when
the benchmark was written: AR(2) serial correlation, cross correlation through
shared latent factors, duty-cycle harmonics, sinh-arcsinh skew and tails.
It runs on the device in float32; the same key gives the same telemetry.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32

DEFAULTS = dict(ar1=0.85, ar2=-0.10, n_harmonics=3, harmonic_amp=0.6,
                cross_rank=4, cross_weight=0.5, skew=0.15, tailweight=1.05,
                mean_scale=10.0, std_scale=1.0)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits and more."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _ar2(key, n_obs, n_series, a1, a2):
    eps = jax.random.normal(key, (n_obs, n_series), F32)

    def step(carry, e):
        y1, y2 = carry
        y = a1 * y1 + a2 * y2 + e
        return (y, y1), y

    _, ys = lax.scan(step, (jnp.zeros(n_series, F32), jnp.zeros(n_series, F32)),
                     eps)
    denom = (1 + a2) * ((1 - a2) ** 2 - a1 ** 2) / (1 - a2)
    return ys / math.sqrt(1.0 / max(denom, 1e-6))


def synthesize(key, n_signals: int, n_obs: int, **params):
    """(n_obs, n_signals) float32 telemetry on the default device."""
    p = dict(DEFAULTS, **params)
    return _synthesize(key, n_signals, n_obs, tuple(sorted(p.items())))


@jax.jit
def _ramp(x, start, signal, slope):
    t = jnp.arange(x.shape[0], dtype=F32)
    ramp = jnp.where(t >= start, (t - start) * slope, 0.0)
    return x.at[:, signal].add(ramp)


def inject_anomaly(x, start: int, signal: int, drift_per_step: float):
    """Additive ramp drift on one signal from ``start`` (an incipient fault)."""
    return _ramp(x, start, signal, jnp.float32(drift_per_step))


def _synthesize_impl(key, n_signals, n_obs, params):
    p = dict(params)
    k_ar, k_lat, k_mix, k_phase, k_freq, k_mean, k_std = jax.random.split(key, 7)
    own = _ar2(k_ar, n_obs, n_signals, p["ar1"], p["ar2"])
    lat = _ar2(k_lat, n_obs, p["cross_rank"], p["ar1"], p["ar2"])
    mix = jax.random.normal(k_mix, (p["cross_rank"], n_signals), F32)
    mix = mix / jnp.linalg.norm(mix, axis=0, keepdims=True)
    w = p["cross_weight"]
    noise = math.sqrt(1 - w * w) * own + w * (lat @ mix)

    t = jnp.arange(n_obs, dtype=F32)[:, None]
    nh = p["n_harmonics"]
    freqs = jax.random.uniform(k_freq, (nh, n_signals), F32,
                               2 * math.pi / n_obs * 2, 2 * math.pi / 64)
    phase = jax.random.uniform(k_phase, (nh, n_signals), F32, 0, 2 * math.pi)
    harm = jnp.zeros((n_obs, n_signals), F32)
    for h in range(nh):
        harm = harm + jnp.sin(t * freqs[h][None, :] + phase[h][None, :])
    harm = harm * (p["harmonic_amp"] / max(nh, 1))

    x = jnp.sinh(p["tailweight"] * jnp.arcsinh(noise) + p["skew"]) + harm
    mean = jax.random.normal(k_mean, (n_signals,), F32) * p["mean_scale"]
    std = jnp.exp(jax.random.normal(k_std, (n_signals,), F32) * 0.3) * p["std_scale"]
    return x * std[None, :] + mean[None, :]


_synthesize = jax.jit(_synthesize_impl, static_argnums=(1, 2, 3))
