"""Share of its roofline the similarity kernel reaches on the surveillance
batches: (memory vectors, batch, signals)."""
from benchlib.readers import kernel_roofline


def read(ctx):
    T = ctx.traced
    return kernel_roofline(ctx, T["m"], T["b"], T["n"])
