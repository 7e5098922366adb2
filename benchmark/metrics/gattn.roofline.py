"""Least time the chip could take for the global layers' attention of the
traced slice's OWN dispatches (costs_wmoe.windowed_attention at the causal
pairs of the histories that rode them: pio_bench/wattn.py) over the device
time of `pio.global_attention` in the slice, %."""
from pio_bench.wattn import roofline


def read(ctx):
    return roofline(ctx, "global_attention", "full_attention", False)
