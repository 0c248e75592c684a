"""Pallas TPU kernel for the MSET2 pairwise-similarity operator.

TPU adaptation of the paper's CUDA similarity kernel (Figure 3): the CUDA
grid/block/warp/thread hierarchy becomes BlockSpec VMEM tiling around the 128x128
MXU. The Euclidean distance is rewritten as ||x||^2 + ||y||^2 - 2 x.y^T so the
dominant cost is an MXU matmul streamed over the signal dimension in K-blocks,
with a fused VPU epilogue applying the nonlinearity — one HBM pass over x and y,
no (m x b x n) intermediate.

Grid: (m/bm, b/bn, n/bk), K innermost; the f32 output block doubles as the
accumulator across K steps (revisited blocks stay resident in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(x_ref, y_ref, x2_ref, y2_ref, o_ref, *, nk: int, gamma: float, kind: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...].astype(F32)          # (bm, bk)
    yb = y_ref[...].astype(F32)          # (bn, bk)
    o_ref[...] += jax.lax.dot_general(
        xb, yb, (((1,), (1,)), ((), ())), preferred_element_type=F32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = o_ref[...]
        d2 = x2_ref[...] + y2_ref[...] - 2.0 * acc   # (bm, 1) + (1, bn)
        d2 = jnp.maximum(d2, 0.0)
        if kind == "inverse_distance":
            o_ref[...] = 1.0 / (1.0 + jnp.sqrt(d2) * (1.0 / gamma))
        else:  # gaussian
            o_ref[...] = jnp.exp(d2 * (-1.0 / (2.0 * gamma * gamma)))


def similarity_pallas(x, y, gamma: float = 1.0, kind: str = "inverse_distance",
                      *, bm: int = 256, bn: int = 256, bk: int = 512,
                      interpret: bool = False):
    """x: (m, n), y: (b, n) -> (m, b) f32 similarity matrix.

    Shapes are padded to block multiples; padding contributes d2=0 terms that are
    sliced away (norms of zero-padded tails are zero, so distances are exact).
    The squared norms travel as (mp, 1) and (1, bp) blocks: Mosaic refuses a
    1-D operand whose XLA layout tiles differently from its own.
    """
    m, n = x.shape
    b, n2 = y.shape
    assert n == n2, (x.shape, y.shape)
    bm_, bn_, bk_ = min(bm, _rup(m, 8)), min(bn, _rup(b, 128)), min(bk, _rup(n, 128))
    mp, bp, np_ = _rup(m, bm_), _rup(b, bn_), _rup(n, bk_)

    xp = jnp.pad(x, ((0, mp - m), (0, np_ - n)))
    yp = jnp.pad(y, ((0, bp - b), (0, np_ - n)))
    x2 = jnp.sum(xp.astype(F32) ** 2, axis=-1, keepdims=True)     # (mp, 1)
    y2 = jnp.sum(yp.astype(F32) ** 2, axis=-1)[None, :]           # (1, bp)

    nk = np_ // bk_
    grid = (mp // bm_, bp // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, gamma=float(gamma), kind=kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm_, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, bp), F32),
        interpret=interpret,
    )(xp, yp, x2, y2)
    return out[:m, :b]


def _rup(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult
