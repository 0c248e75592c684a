"""Nominal flops of the decode steps of the untraced window
(``benchlib.lm_work.decode_flops``), over its length and the chip's bf16 peak."""
from benchlib import lm_work
from benchlib.readers import mfu


def read(ctx):
    L = ctx.layer
    if not L.get("calls"):
        return None
    flops = lm_work.decode_flops(ctx.config, L["batch"], L["calls"], L["ctx_sum"])
    return mfu(ctx, flops / L["calls"])
