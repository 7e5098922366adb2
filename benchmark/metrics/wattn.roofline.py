"""Least time the chip could take for the window layers' attention of the
traced slice's OWN dispatches (costs_wmoe.windowed_attention at the pairs
`min(position + 1, 4,096)` of the histories that rode them — joined through
the spans' `seq`, the request traces' `meta.dispatch_seq` and the length law:
pio_bench/wattn.py — 2 x (128 + 128) flops a pair and QUERY head; q and o
once per query head, k and v once per KV head) over the device time of
`pio.window_attention` in the slice, %."""
from pio_bench.wattn import roofline


def read(ctx):
    return roofline(ctx, "window_attention", "sliding_attention", True)
