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
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

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
    Xf = X.astype(F32)
    mean = jnp.mean(Xf, axis=0)
    std = jnp.std(Xf, axis=0) + 1e-6
    Xs = (Xf - mean) / std

    D, _ = build_memory_matrix(Xs, n_memvec)
    g = float(gamma) if gamma is not None else float(_bandwidth(D))

    G = similarity(D, D, gamma=g, kind=kind, impl=impl)          # (m, m)
    # regularized pseudo-inverse via eigendecomposition (the paper's cuSOLVER
    # step). The eigh runs in float32 LAPACK on the host: a TPU eigh of
    # thousands of memory vectors compiles for minutes. The product stays on
    # the device, at full f32 precision as in estimate()
    m = G.shape[0]
    evals, evecs = np.linalg.eigh(np.asarray(G, np.float32)
                                  + np.float32(reg) * np.eye(m, dtype=np.float32))
    evals, evecs = jnp.asarray(evals), jnp.asarray(evecs)
    inv_evals = jnp.where(evals > reg, 1.0 / evals, 0.0)
    Ginv = jnp.matmul(evecs * inv_evals[None, :], evecs.T, precision="highest")
    return MSETModel(D=D, Ginv=Ginv, gamma=g, kind=kind, mean=mean, std=std)


@partial(jax.jit, static_argnames=("impl",))
def estimate(model: MSETModel, X, impl: str = "auto"):
    """X: (b, n) observations -> (x_hat (b, n), residuals (b, n))."""
    Xs = (X.astype(F32) - model.mean) / model.std
    K = similarity(model.D, Xs, gamma=model.gamma, kind=model.kind, impl=impl)
    # full f32 products: Ginv's eigenvalue inverses reach 1/reg, which the
    # TPU's default one-pass bf16 matmul does not resolve
    W = jnp.matmul(model.Ginv, K, precision="highest")           # (m, b)
    Xhat_s = jnp.matmul(W.T, model.D, precision="highest")       # (b, n)
    Xhat = Xhat_s * model.std + model.mean
    return Xhat, X - Xhat


def surveil(model: MSETModel, X_stream, impl: str = "auto"):
    """Convenience: full-stream estimation. X_stream: (T, n)."""
    return estimate(model, X_stream, impl=impl)
