"""Share of the worker's dispatches (all but the inline ones) whose program
was launched before the previous dispatch's device_compute returned:
launch-ahead's engagement, from MicroBatcher.stats() deltas, %.  A program
without the counter (the parent of ISSUE 40) gives nothing to read."""
from pio_bench.readers import delta


def read(ctx):
    n = delta(ctx, "batcher.ahead_batches")
    b, i = delta(ctx, "batcher.batches"), delta(ctx, "batcher.inline_batches")
    if None in (n, b, i) or b - i <= 0:
        return None
    return 100.0 * n / (b - i)
