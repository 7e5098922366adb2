"""Median of the readback stage `d2h` (the two device-to-host copies after
the device program returned) over the traced requests that have it, ms."""
from pio_bench.readers import pct


def read(ctx):
    vals = [t["stagesMs"]["d2h"] for t in ctx["traces"]
            if t.get("status") == 200 and "d2h" in (t.get("stagesMs") or {})]
    return pct(vals, 50)
