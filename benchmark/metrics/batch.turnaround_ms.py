"""Mean time from one dispatch's device program returning to the next one's
launch, over the dispatches that ended with rows still waiting: the device
waiting for the host with work at hand.  MicroBatcher.stats() deltas, ms."""
from pio_bench.readers import delta


def read(ctx):
    s, n = (delta(ctx, "batcher.turnaround_ms_sum"),
            delta(ctx, "batcher.turnaround_n"))
    return s / n if s is not None and n else None
