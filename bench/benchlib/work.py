"""Nominal operations and bytes, counted from shapes, whatever implements them.

These are the yardstick of the roofline and mfu metrics. A float32 product at
``precision="highest"`` takes several bfloat16 passes on the MXU, but it is
counted once here, against the chip's one bfloat16 peak: the shares read low
by that factor, and a change of precision moves them.
"""
from __future__ import annotations

F32_BYTES = 4


def similarity(m: int, b: int, n: int) -> tuple:
    """(flops, bytes) of the similarity of ``m`` memory vectors with ``b``
    observations of ``n`` signals: the 2mbn of the cross product (the norms
    and the elementwise epilogue are left out), and one read of each operand
    with its norms and one write of the (m, b) result."""
    flops = 2.0 * m * b * n
    bytes_ = F32_BYTES * (m * n + b * n + m + b + m * b)
    return flops, bytes_


def estimate(m: int, b: int, n: int) -> float:
    """Flops of one MSET2 estimate of a batch: the similarity (2mbn), the
    weights ``Ginv K`` (2m^2 b) and the reconstruction ``W^T D`` (2bmn)."""
    return 2.0 * m * b * n + 2.0 * m * m * b + 2.0 * b * m * n


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> tuple:
    """(share in %, bound) of the least time the chip could take over the
    time it took; ``bound`` names the limit that sets the least time."""
    t_flops, t_bytes = flops / peak_flops, bytes_ / peak_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
