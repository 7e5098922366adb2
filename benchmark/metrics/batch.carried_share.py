"""Rows the bucket cut left for a later dispatch over rows dispatched, from
MicroBatcher.stats() deltas, %."""
from pio_bench.readers import delta


def read(ctx):
    c, q = delta(ctx, "batcher.carried_rows"), delta(ctx, "batcher.queries")
    return 100.0 * c / q if c is not None and q else None
