"""Median of h2d + device_compute per traced request (host clock; ends in
the readback), ms."""
from pio_bench.readers import pct, stage_values


def read(ctx):
    vals = [v for v in stage_values(ctx, ("h2d", "device_compute")) if v > 0]
    return pct(vals, 50)
