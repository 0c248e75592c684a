"""MSET2 — Multivariate State Estimation Technique (nonlinear nonparametric
regression for prognostic surveillance), the paper's pluggable ML workload.

Training (paper Fig. 4 cost driver):
    D     = memory matrix, (m, n) selected from training data
    G     = D (x) D  — the nonlinear similarity operator (the CUDA/Pallas hot spot)
    Ginv  = regularized pseudo-inverse of G (eigendecomposition on the host)

Surveillance (paper Fig. 5 cost driver), streamed over observations x:
    w     = Ginv · (D (x) x)
    x_hat = w^T · D
residuals x - x_hat feed the SPRT detector (sprt.py).

Under a telemetry session (``repro.telemetry``) ``train`` and ``estimate``
record host spans: ``mset.train`` with ``.select``, ``.similarity``, ``.d2h``
(``G`` to the host), ``.eigh``, ``.h2d`` and ``.ginv``; ``mset.estimate``
around the compiled program. A span times the host: a span that dispatches
device work closes before that work ends, and the next one that waits for it
holds the wait. Device time comes from the device trace.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.kernels.similarity import similarity
from repro.mset.memory_vectors import build_memory_matrix

F32 = jnp.float32


@dataclass
class MSETModel:
    D: jax.Array          # (m, n) memory matrix
    Ginv: jax.Array       # (m, m)
    gamma: float
    kind: str
    mean: jax.Array       # (n,) standardization
    std: jax.Array        # (n,)

    def tree_flatten(self):
        return (self.D, self.Ginv, self.mean, self.std), (self.gamma, self.kind)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        D, Ginv, mean, std = leaves
        gamma, kind = aux
        return cls(D, Ginv, gamma, kind, mean, std)


jax.tree_util.register_pytree_node(
    MSETModel, MSETModel.tree_flatten, MSETModel.tree_unflatten)


def _bandwidth(D) -> jax.Array:
    """Median-distance heuristic for gamma, from a subsample of D."""
    s = D[: min(256, D.shape[0])]
    x2 = jnp.sum(s * s, axis=1)
    d2 = jnp.maximum(x2[:, None] + x2[None, :] - 2 * s @ s.T, 0.0)
    med = jnp.median(jnp.sqrt(d2 + jnp.eye(s.shape[0]) * 1e9 * 0.0))
    return jnp.maximum(med, 1e-3)


def train(X, n_memvec: int, *, kind: str = "inverse_distance",
          gamma: Optional[float] = None, reg: float = 1e-6,
          impl: str = "auto") -> MSETModel:
    """X: (n_obs, n_signals) raw training telemetry."""
    with telemetry.span("mset.train", n_memvec=n_memvec, kind=kind):
        with telemetry.span("mset.train.select"):
            Xf = X.astype(F32)
            mean = jnp.mean(Xf, axis=0)
            std = jnp.std(Xf, axis=0) + 1e-6
            Xs = (Xf - mean) / std

            D, _ = build_memory_matrix(Xs, n_memvec)
            g = float(gamma) if gamma is not None else float(_bandwidth(D))

        with telemetry.span("mset.train.similarity"):
            G = similarity(D, D, gamma=g, kind=kind, impl=impl)      # (m, m)
        # regularized pseudo-inverse via eigendecomposition (the paper's
        # cuSOLVER step). The eigh runs in float32 LAPACK on the host: a TPU
        # eigh of thousands of memory vectors compiles for minutes. The
        # product stays on the device, at full f32 precision as in estimate()
        m = G.shape[0]
        with telemetry.span("mset.train.d2h"):
            G_host = np.asarray(G, np.float32)
        with telemetry.span("mset.train.eigh"):
            evals, evecs = np.linalg.eigh(
                G_host + np.float32(reg) * np.eye(m, dtype=np.float32))
        with telemetry.span("mset.train.h2d"):
            evals, evecs = jnp.asarray(evals), jnp.asarray(evecs)
        with telemetry.span("mset.train.ginv"):
            inv_evals = jnp.where(evals > reg, 1.0 / evals, 0.0)
            Ginv = jnp.matmul(evecs * inv_evals[None, :], evecs.T,
                              precision="highest")
    return MSETModel(D=D, Ginv=Ginv, gamma=g, kind=kind, mean=mean, std=std)


def _estimate(model: MSETModel, X, impl: str = "auto"):
    Xs = (X.astype(F32) - model.mean) / model.std
    K = similarity(model.D, Xs, gamma=model.gamma, kind=model.kind, impl=impl)
    # full f32 products: Ginv's eigenvalue inverses reach 1/reg, which the
    # TPU's default one-pass bf16 matmul does not resolve
    W = jnp.matmul(model.Ginv, K, precision="highest")           # (m, b)
    Xhat_s = jnp.matmul(W.T, model.D, precision="highest")       # (b, n)
    Xhat = Xhat_s * model.std + model.mean
    return Xhat, X - Xhat


# the compiled program keeps the name jit_estimate in device traces
_estimate.__name__ = _estimate.__qualname__ = "estimate"
_estimate_jit = jax.jit(_estimate, static_argnames=("impl",))


def estimate(model: MSETModel, X, impl: str = "auto"):
    """X: (b, n) observations -> (x_hat (b, n), residuals (b, n)). One
    compiled program; callable inside ``jit``."""
    with telemetry.span("mset.estimate"):
        return _estimate_jit(model, X, impl=impl)


def surveil(model: MSETModel, X_stream, impl: str = "auto"):
    """Convenience: full-stream estimation. X_stream: (T, n)."""
    return estimate(model, X_stream, impl=impl)
