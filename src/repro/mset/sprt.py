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


# rows of each output a loop iteration writes, as one aligned block: the
# recursion's k restarted steps run unrolled inside the iteration. Of 8 (the
# sublane tile), 16, 32 and 64, 32 ran the loop fastest at (512, 1024) on a
# TPU v5e chip (PERF.md section 6).
SPRT_BLOCK = 32


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
    T, n = r.shape
    k = SPRT_BLOCK

    def steps(sp, sn, ip, in_):
        """The recursion over the rows of (rows, n) increments, unrolled;
        returns the state after them and their (rows, n) outputs."""
        rows = []
        for t in range(ip.shape[0]):
            sp = jnp.clip(sp + ip[t], lo, None)
            sn = jnp.clip(sn + in_[t], lo, None)
            alarm = (sp >= hi) | (sn >= hi)
            # reset after decision (classic SPRT restart)
            sp = jnp.where(sp >= hi, 0.0, sp)
            sn = jnp.where(sn >= hi, 0.0, sn)
            rows.append((alarm, sp, sn))
        return sp, sn, tuple(jnp.stack(o) for o in zip(*rows))

    def block(i, carry):
        sp, sn, outs = carry
        t = i * k
        sp, sn, new = steps(sp, sn, lax.dynamic_slice_in_dim(inc_pos, t, k),
                            lax.dynamic_slice_in_dim(inc_neg, t, k))
        outs = tuple(lax.dynamic_update_slice_in_dim(o, b, t, 0)
                     for o, b in zip(outs, new))
        return sp, sn, outs

    sp = sn = jnp.zeros(n, F32)
    outs = (jnp.zeros((T, n), bool), jnp.zeros((T, n), F32),
            jnp.zeros((T, n), F32))
    # the loop carries the (T, n) outputs and writes T // k blocks; the last
    # T % k rows follow it, unrolled, as one block of their own
    if T >= k:
        sp, sn, outs = lax.fori_loop(0, T // k, block, (sp, sn, outs))
    tail = T % k
    if tail:
        _, _, new = steps(sp, sn, inc_pos[T - tail:], inc_neg[T - tail:])
        outs = tuple(o.at[T - tail:].set(b) for o, b in zip(outs, new))
    return outs


# the compiled program keeps the name jit_sprt in device traces; it lowers
# once per (shapes, dtypes, params) and is reused on every later call
_sprt.__name__ = _sprt.__qualname__ = "sprt"
_sprt_jit = jax.jit(_sprt, static_argnames=("p",))


def sprt(residuals, sigma, p: SPRTParams = SPRTParams(), mu=None):
    """residuals: (T, n); sigma/mu: (n,) residual std/mean from clean validation
    data (mu defaults to 0). Returns (alarms (T, n), llr_pos, llr_neg). Each
    call starts the test from zero. One compiled program; callable inside
    ``jit``. In a telemetry session, ``mset_sprt_rows_total{part}`` counts
    the rows done in whole blocks of ``SPRT_BLOCK`` and in the tail after
    them (inside ``jit``, once per trace)."""
    with telemetry.span("mset.sprt"):
        T = residuals.shape[0]
        telemetry.counter("mset_sprt_rows_total", T - T % SPRT_BLOCK,
                          part="block")
        telemetry.counter("mset_sprt_rows_total", T % SPRT_BLOCK, part="tail")
        return _sprt_jit(residuals, sigma, mu, p=p)


def empirical_false_alarm_rate(alarms) -> jax.Array:
    return jnp.mean(alarms.astype(F32))
