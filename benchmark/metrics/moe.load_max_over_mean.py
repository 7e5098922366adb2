"""The busiest expert's tokens over the mean load, per sparse layer of a
dispatch, averaged over the window (the packed scorer's counters): 1 is a
flat load; the grouped product's row tiles are as uneven as this."""
from pio_bench.readers import delta


def read(ctx):
    s = delta(ctx, "fastpath.load_max_over_mean_sum")
    n = delta(ctx, "fastpath.sparse_layer_dispatches")
    return s / n if s is not None and n else None
