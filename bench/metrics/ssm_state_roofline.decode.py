"""Share of its roofline the decode update of the float32 SSM state reaches:
the ops of the traced window that read or write the (batch, heads, d_state,
head_dim) state, with any leading stack dimension, copies left out, against
``lm_work.ssm_state_update`` for every Mamba layer of every step, whichever of
the chip's peaks bounds it."""
from benchlib import lm_work, trace, work


def state_ops(b: int, h: int, n: int, p: int) -> str:
    """Ops other than copies whose text holds the f32 state's shape."""
    return rf"^\S* %(?!copy)[\w.-]+ = .*\bf32\[(?:\d+,)*{b},{h},{n},{p}\]"


def read(ctx):
    c, T = ctx.config, ctx.traced
    if ctx.trace is None or ctx.peaks is None or not T.get("calls"):
        return None
    b, h = T["batch"], c["mamba_n_heads"]
    n, p = c["mamba_d_state"], c["mamba_d_head"]
    durs = trace.op_durations(ctx.trace, state_ops(b, h, n, p))
    if not durs or sum(durs) <= 0:
        return None
    layers = T["calls"] * sum(k == "mamba" for k in c["layer_types"])
    flops, bytes_ = lm_work.ssm_state_update(b, h, p, n)
    share, _ = work.roofline_share(flops * layers, bytes_ * layers, sum(durs),
                                   ctx.peaks["bf16_flops"],
                                   ctx.peaks["hbm_bytes_per_s"])
    return share
