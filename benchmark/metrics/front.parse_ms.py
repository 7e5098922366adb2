"""Median per answered traced request of `meta.parse_ms`: request line
arrived -> the trace is born (headers parsed, body read), ms.  Outside the
trace's wall, so in no stage and not in `front.self_ms`."""
from pio_bench import hostjoin
from pio_bench.readers import pct


def read(ctx):
    return pct(list(hostjoin.meta_values(ctx, "parse_ms").values()), 50)
