"""MSET2 as a sharded cloud service: batched fleet surveillance under pjit.

The estimation math shards naturally: memory vectors (m) over the ``model`` axis,
the observation batch over (pod, data). GSPMD inserts one all-reduce for the
x_hat contraction over m — this is the service the paper deploys in containers,
here mapped onto a TPU slice.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.similarity import similarity_ref
from repro.mset.mset2 import MSETModel

F32 = jnp.float32


def _estimate_sharded(D, Ginv, mean, std, X, *, gamma, kind):
    Xs = (X.astype(F32) - mean) / std
    K = similarity_ref(D, Xs, gamma, kind)      # (m, b)
    W = jnp.matmul(Ginv, K, precision="highest")     # (m, b), as mset2
    Xhat = jnp.matmul(W.T, D, precision="highest")   # (b, n)
    Xhat = Xhat * std + mean
    return Xhat, X - Xhat


def make_service(model: MSETModel, mesh: Mesh, kind: Optional[str] = None):
    """Returns a jitted estimate(X (b, n)) with production shardings."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mvec = "model" if "model" in mesh.axis_names else None
    s_D = NamedSharding(mesh, P(mvec, None))
    s_G = NamedSharding(mesh, P(mvec, None))
    s_v = NamedSharding(mesh, P(None))
    s_X = NamedSharding(mesh, P(batch_axes, None))

    fn = jax.jit(
        partial(_estimate_sharded, gamma=model.gamma, kind=kind or model.kind),
        in_shardings=(s_D, s_G, s_v, s_v, s_X),
        out_shardings=(s_X, s_X),
        static_argnames=(),
    )

    def estimate(X):
        return fn(model.D, model.Ginv, model.mean, model.std, X)

    estimate.lower = lambda X: fn.lower(model.D, model.Ginv, model.mean, model.std, X)
    return estimate


def service_flops_bytes(n_signals: int, n_memvec: int, batch: int):
    """Analytic per-call cost of ``_estimate_sharded`` on a batch of
    observations: similarity kernel (K = sim(D, X)), weight solve (W = Ginv K),
    reconstruction (Xhat = W^T D). Feeds the fleet scenario's roofline rows."""
    m, n, b = n_memvec, n_signals, batch
    flops = 2.0 * m * b * n + 2.0 * m * m * b + 2.0 * b * m * n
    bytes_ = 4.0 * (m * n + m * m        # D, Ginv (weight streaming)
                    + 3 * b * n          # X in, Xhat + residual out
                    + 2 * m * b)         # K, W intermediates
    return flops, bytes_


def service_collective_bytes(n_signals: int, batch: int) -> float:
    """All-reduce traffic of the x_hat contraction over the sharded m axis."""
    return 2.0 * 4.0 * batch * n_signals   # ring all-reduce ~ 2x payload


def abstract_service_inputs(n_signals: int, n_memvec: int, batch: int):
    """ShapeDtypeStructs for dry-run scoping of the MSET service."""
    return {
        "D": jax.ShapeDtypeStruct((n_memvec, n_signals), F32),
        "Ginv": jax.ShapeDtypeStruct((n_memvec, n_memvec), F32),
        "mean": jax.ShapeDtypeStruct((n_signals,), F32),
        "std": jax.ShapeDtypeStruct((n_signals,), F32),
        "X": jax.ShapeDtypeStruct((batch, n_signals), F32),
    }
