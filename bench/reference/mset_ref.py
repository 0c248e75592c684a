"""Plain MSET2 and SPRT, the reference that decides the MSET cells' ``correct``.

Written from the method as the paper and the program's docstrings state it,
importing nothing of the program:

* standardize the training telemetry (mean, std + 1e-6);
* memory vectors: every observation that holds some signal's minimum or
  maximum, then observations sampled at equal steps along the order of their
  norms;
* the inverse-distance similarity ``1 / (1 + d / gamma)``, gamma as the
  configuration states it;
* ``Ginv`` the regularised pseudo-inverse of ``G + reg I`` by its
  eigendecomposition (eigenvalues at or below ``reg`` dropped);
* ``x_hat = (Ginv K)^T D``, de-standardized; residuals ``x - x_hat``;
* a two-sided SPRT for a mean shift of ``m_shift`` sigmas, restarted after
  each decision.

``Plain`` computes in float32 numpy on the host, every product in full
float32: the precision the configuration states. ``Control`` is the same
arithmetic in float32 with every matrix product at three bfloat16 passes (the
TPU's ``precision="high"``), emulated with explicit bfloat16 splits so that it
reads the same on any backend: the step below.
"""
from __future__ import annotations

import math

import numpy as np

class Plain:
    """float32 numpy arithmetic on the host, full float32 products."""

    def asarray(self, a):
        return np.asarray(a, np.float32)

    def matmul(self, a, b):
        return a @ b

    def host(self, a):
        return np.asarray(a, np.float32)


class Control:
    """float32 with three-pass bfloat16 products, on JAX's default device."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        def split(a):
            hi = a.astype(jnp.bfloat16)
            return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def dot(a, b):
            return jnp.matmul(a, b, preferred_element_type=jnp.float32)

        @jax.jit
        def matmul3(a, b):
            ah, al = split(a)
            bh, bl = split(b)
            return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))

        self._jnp = jnp
        self._matmul = matmul3

    def asarray(self, a):
        return self._jnp.asarray(np.asarray(a, np.float32))

    def matmul(self, a, b):
        return self._matmul(a, b)

    def host(self, a):
        return np.asarray(a, np.float32)


def _xp(ar):
    return np if isinstance(ar, Plain) else ar._jnp


def select_memory_vectors(Xs, n_memvec: int) -> np.ndarray:
    """Indices of the memory vectors: min-max envelope first, then equal
    steps along the norm order. ``Xs`` is a host array."""
    n_obs, n_sig = Xs.shape
    envelope = np.concatenate([np.argmin(Xs, axis=0), np.argmax(Xs, axis=0)])
    order = np.argsort(np.sqrt((Xs * Xs).sum(axis=1)), kind="stable")
    take = np.linspace(0, n_obs - 1, n_memvec).astype(np.int32)
    n_env = min(2 * n_sig, n_memvec)
    return np.concatenate([envelope[:n_env], order[take][:n_memvec - n_env]])


def similarity(ar, A, B, gamma: float):
    """(len(A), len(B)) inverse-distance similarity."""
    xp = _xp(ar)
    a2 = (A * A).sum(axis=1)[:, None]
    b2 = (B * B).sum(axis=1)[None, :]
    d2 = xp.maximum(a2 + b2 - 2.0 * ar.matmul(A, B.T), 0.0)
    return 1.0 / (1.0 + xp.sqrt(d2) / gamma)


def train(ar, X, n_memvec: int, reg: float, gamma: float) -> dict:
    """The trained model as a dict of arrays of ``ar``'s kind (gamma a float)."""
    xp = _xp(ar)
    Xf = ar.asarray(X)
    mean = Xf.mean(axis=0)
    std = xp.sqrt(((Xf - mean) ** 2).mean(axis=0)) + 1e-6
    Xs = (Xf - mean) / std
    idx = select_memory_vectors(ar.host(Xs), n_memvec)
    D = Xs[idx]
    G = ar.host(similarity(ar, D, D, gamma))
    dtype = G.dtype
    evals, evecs = np.linalg.eigh(G + dtype.type(reg) * np.eye(len(G), dtype=dtype))
    inv = np.where(evals > reg, 1.0 / evals, 0.0).astype(dtype)
    Ginv = ar.matmul(ar.asarray(evecs * inv[None, :]), ar.asarray(evecs.T))
    return {"D": D, "Ginv": Ginv, "gamma": gamma, "mean": mean, "std": std}


def estimate(ar, model: dict, X):
    """(x_hat, residuals) as host arrays."""
    Xf = ar.asarray(X)
    Xs = (Xf - model["mean"]) / model["std"]
    K = similarity(ar, model["D"], Xs, model["gamma"])
    W = ar.matmul(model["Ginv"], K)
    x_hat = ar.matmul(W.T, model["D"]) * model["std"] + model["mean"]
    return ar.host(x_hat), ar.host(Xf - x_hat)


def sprt(residuals, sigma, mu, alpha=1e-3, beta=1e-3, m_shift=3.0):
    """(T, n) boolean alarms of the restarted two-sided SPRT."""
    r = (np.asarray(residuals, np.float32) - mu) / sigma
    hi = math.log((1 - beta) / alpha)
    lo = math.log(beta / (1 - alpha))
    inc_pos = m_shift * r - 0.5 * m_shift ** 2
    inc_neg = -m_shift * r - 0.5 * m_shift ** 2
    sp = np.zeros(r.shape[1])
    sn = np.zeros(r.shape[1])
    alarms = np.zeros(r.shape, bool)
    for t in range(r.shape[0]):
        sp = np.maximum(sp + inc_pos[t], lo)
        sn = np.maximum(sn + inc_neg[t], lo)
        alarms[t] = (sp >= hi) | (sn >= hi)
        sp = np.where(sp >= hi, 0.0, sp)
        sn = np.where(sn >= hi, 0.0, sn)
    return alarms
