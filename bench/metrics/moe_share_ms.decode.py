"""Device milliseconds a decode step of the expert-share layers: the ops of
the traced window that hold the (batch, experts) router logits or their
(batch, top-k) choice, the held experts' (held, d, ff) and (held, ff, d)
weights, or their (held, batch, ff) products; the union of their time over
the steps."""
from benchlib import trace


def share_ops(b: int, e: int, k: int, held: int, d: int, ff: int) -> str:
    return (rf"\[{b},{e}\]|\[{b},{k}\]|\[(?:\d+,)?{held},{d},{ff}\]"
            rf"|\[(?:\d+,)?{held},{ff},{d}\]|\[{held},{b},{ff}\]")


def read(ctx):
    c, T = ctx.config, ctx.traced
    if ctx.trace is None or not T.get("calls"):
        return None
    busy = trace.op_busy_s(ctx.trace, share_ops(
        T["batch"], c["deployment"]["experts_total"], c["num_experts_per_tok"],
        c["num_local_experts"], c["hidden_size"], c["intermediate_size"]))
    return 1e3 * busy / T["calls"] if busy > 0 else None
