"""Padded tokens over tokens computed (padding included), from the packed
scorer's counters over the window, %."""
from pio_bench.readers import delta


def read(ctx):
    pad, real = (delta(ctx, "fastpath.padded_tokens"),
                 delta(ctx, "fastpath.tokens"))
    if pad is None or not real:
        return None
    return 100.0 * pad / (pad + real)
