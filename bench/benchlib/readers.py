"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Device time comes from the traced window (``ctx.trace``, its counts in
``ctx.traced``); host-clock counts from the untraced window (``ctx.layer``).
An operation is found by what it computes, its shapes in the HLO text, and
not by the name of the program it runs in, which a later change may alter.
"""
from __future__ import annotations

from benchlib import trace, work


def similarity_kernel(m: int, b: int, n: int) -> str:
    """The similarity kernel at (m, b, n), wherever it is called from: a
    Pallas custom call whose (m, b) result is made from the (m, n) memory
    vectors and the (b, n) observations."""
    return (rf"= f32\[{m},{b}\]\S* custom-call\(f32\[{m},{n}\]\S* \S+, "
            rf"f32\[{b},{n}\].*tpu_custom_call")


def sprt_scan(b: int, n: int) -> str:
    """SPRT's sequential scan over ``b`` observations of ``n`` signals, in
    whatever program it runs: a while loop that carries the (b, n) alarms."""
    return rf"%while[\w.-]* = \(.*?\bpred\[{b},{n}\]"


def kernel_roofline(ctx, m: int, b: int, n: int):
    """% of the roofline of the similarity kernel at (m, b, n): the least
    time of the launches in the traced window over their device time; None
    where the trace holds no launch."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    durs = trace.op_durations(ctx.trace, similarity_kernel(m, b, n))
    if not durs or sum(durs) <= 0:
        return None
    flops, bytes_ = work.similarity(m, b, n)
    share, _ = work.roofline_share(flops * len(durs), bytes_ * len(durs),
                                   sum(durs), ctx.peaks["bf16_flops"],
                                   ctx.peaks["hbm_bytes_per_s"])
    return share


def mfu(ctx, flops_per_call: float):
    """% of the chip's bf16 peak: nominal flops of the calls completed in
    the untraced window over its length."""
    L = ctx.layer
    if ctx.peaks is None or not L.get("calls") or not L.get("elapsed_s"):
        return None
    return (100.0 * flops_per_call * L["calls"] / L["elapsed_s"]
            / (ctx.peaks["bf16_flops"] * len(ctx.chips)))


def idle_share(ctx):
    """% of the traced window in which no operation ran on the device."""
    if ctx.trace is None or ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace, ctx.chips)
                    / ctx.trace.window_s)
