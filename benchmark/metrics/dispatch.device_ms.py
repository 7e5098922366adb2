"""Median over the traced slice's dispatches of (last program event's end −
first's start) inside that dispatch's `pio.device_compute` span, ms: the
device's part of ONE dispatch, the same statistic as `fastpath.dispatch_ms`
(`pio_bench/hostjoin.py`)."""
from pio_bench import hostjoin


def read(ctx):
    return hostjoin.dispatch_median(ctx, "device_ms")
