"""p95 of (answer received - due) over the window's answered requests, ms:
what `serve.p95_ms` is, read as a per-layer metric without a bound in the
sequence family's cells, where it spreads by more than a bound can hold
(PERF.md section 2)."""
from pio_bench.readers import latencies_ms, pct


def read(ctx):
    lat = latencies_ms(ctx["good"])
    return pct(lat, 95) if lat else None
