"""Device milliseconds per batch of the SPRT's sequential scan: the time in
which its loop ran (the loop's body ops nest inside the loop op), over the
batches of the traced window."""
from benchlib import trace
from benchlib.readers import sprt_scan


def read(ctx):
    T = ctx.traced
    if ctx.trace is None or not T.get("calls"):
        return None
    busy = trace.op_busy_s(ctx.trace, sprt_scan(T["b"], T["n"]))
    return 1e3 * busy / T["calls"] if busy > 0 else None
