"""Padded rows over rows dispatched (padding included), from
BucketedScorer.stats() deltas, %."""
from pio_bench.readers import delta


def read(ctx):
    pad, q = delta(ctx, "fastpath.padded_rows"), delta(ctx, "fastpath.queries")
    if pad is None or not q:
        return None
    return 100.0 * pad / (pad + q)
