"""SPRT (Sequential Probability Ratio Test) fault detection on MSET residuals —
the alarming stage that gives MSET2 its "ultra-low false/missed-alarm
probabilities" (paper §II.B). Two-sided mean-shift test, vectorized over signals.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import telemetry

F32 = jnp.float32


@dataclass(frozen=True)
class SPRTParams:
    alpha: float = 1e-3      # false-alarm probability
    beta: float = 1e-3       # missed-alarm probability
    m_shift: float = 3.0     # magnitude of mean shift to detect, in sigmas

    # float32 logs taken on the host: the compiled program holds them as
    # constants, and no call waits on the device for them
    @property
    def upper(self) -> float:
        return float(np.log(np.float32((1 - self.beta) / self.alpha)))

    @property
    def lower(self) -> float:
        return float(np.log(np.float32(self.beta / (1 - self.alpha))))


def _sprt(residuals, sigma, mu, p: SPRTParams):
    r = residuals.astype(F32)
    if mu is not None:
        r = r - mu[None, :].astype(F32)
    r = r / sigma[None, :].astype(F32)
    M = p.m_shift
    # log-likelihood ratio increments for H1: mean=+M vs H0: mean=0 (unit var)
    inc_pos = M * r - 0.5 * M * M
    inc_neg = -M * r - 0.5 * M * M
    hi, lo = p.upper, p.lower

    def step(carry, inc):
        sp, sn = carry
        ip, in_ = inc
        sp = jnp.clip(sp + ip, lo, None)
        sn = jnp.clip(sn + in_, lo, None)
        alarm = (sp >= hi) | (sn >= hi)
        # reset after decision (classic SPRT restart)
        sp = jnp.where(sp >= hi, 0.0, sp)
        sn = jnp.where(sn >= hi, 0.0, sn)
        return (sp, sn), (alarm, sp, sn)

    z = jnp.zeros(r.shape[1], F32)
    _, (alarms, sp, sn) = lax.scan(step, (z, z), (inc_pos, inc_neg))
    return alarms, sp, sn


# the compiled program keeps the name jit_sprt in device traces; it lowers
# once per (shapes, dtypes, params) and is reused on every later call
_sprt.__name__ = _sprt.__qualname__ = "sprt"
_sprt_jit = jax.jit(_sprt, static_argnames=("p",))


def sprt(residuals, sigma, p: SPRTParams = SPRTParams(), mu=None):
    """residuals: (T, n); sigma/mu: (n,) residual std/mean from clean validation
    data (mu defaults to 0). Returns (alarms (T, n), llr_pos, llr_neg). Each
    call starts the test from zero. One compiled program; callable inside
    ``jit``."""
    with telemetry.span("mset.sprt"):
        return _sprt_jit(residuals, sigma, mu, p=p)


def empirical_false_alarm_rate(alarms) -> jax.Array:
    return jnp.mean(alarms.astype(F32))
