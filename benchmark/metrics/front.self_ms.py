"""Median per request of the HTTP front's own stages (decode + serialize +
other), from the server's request traces, ms."""
from pio_bench.readers import pct, stage_values


def read(ctx):
    return pct(stage_values(ctx, ("decode", "serialize", "other")), 50)
