"""Real tokens the state-space scan was asked over the token slots of the
chunks it ran (`scan_tokens` / (`scan_chunks` x the chunk size), from the
packed scorer's counters over the window), %: what alignment to chunks
costs the scan (a rung's padded tail is not run)."""
from pio_bench.readers import delta


def read(ctx):
    tokens, chunks = (delta(ctx, "fastpath.scan_tokens"),
                      delta(ctx, "fastpath.scan_chunks"))
    size = ctx["counters_after"].get("fastpath.scan_chunk")
    if tokens is None or not chunks or not size:
        return None
    return 100.0 * tokens / (chunks * size)
