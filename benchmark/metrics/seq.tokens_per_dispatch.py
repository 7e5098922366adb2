"""Real tokens per dispatch over the window (the packed scorer's
counters)."""
from pio_bench.xplane_named import per_dispatch


def read(ctx):
    return per_dispatch(ctx, "fastpath.tokens")
