"""Queries per batch over the window, from MicroBatcher.stats() deltas."""
from pio_bench.readers import delta


def read(ctx):
    q, b = delta(ctx, "batcher.queries"), delta(ctx, "batcher.batches")
    return q / b if q and b else None
