"""Median over the traced slice's dispatches of (first device op's start −
its `pio.device_compute` span's start), ms, on the device clock as
`pio_bench/hostjoin.py` shifts it: the jitted call's enqueue and the device
picking the program up."""
from pio_bench import hostjoin


def read(ctx):
    return hostjoin.dispatch_median(ctx, "launch_ms")
