"""Share of dispatches run inline on a request's own thread (one row; at
this width it costs the same sweep as 64), from MicroBatcher.stats()
deltas, %."""
from pio_bench.readers import delta


def read(ctx):
    n, b = delta(ctx, "batcher.inline_batches"), delta(ctx, "batcher.batches")
    return 100.0 * n / b if n is not None and b else None
