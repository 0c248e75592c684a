"""Nominal flops of MSET2 estimate per batch, times batches per second, over
the chip's bf16 peak."""
from benchlib import work
from benchlib.readers import mfu


def read(ctx):
    L = ctx.layer
    return mfu(ctx, work.estimate(L["m"], L["b"], L["n"]))
