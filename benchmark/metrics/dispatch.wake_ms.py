"""Median over the traced slice's dispatches of (its `pio.device_compute`
span's end − last device op's end), ms: the host noticing that the program
has finished and `block_until_ready` returning.  Per dispatch launch +
device + wake tile the span (`pio_bench/hostjoin.py`)."""
from pio_bench import hostjoin


def read(ctx):
    return hostjoin.dispatch_median(ctx, "wake_ms")
