"""p95 of (actually sent - due) in the generator process, ms: a guard — a
starved generator reads as a fast server."""
from pio_bench.readers import lateness_ms, pct


def read(ctx):
    return pct(lateness_ms(ctx["records"]), 95)
