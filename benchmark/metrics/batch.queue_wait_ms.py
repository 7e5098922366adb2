"""p95 of the batcher's queue_wait stage, ms."""
from pio_bench.readers import pct, stage_values


def read(ctx):
    return pct(stage_values(ctx, ("queue_wait",)), 95)
