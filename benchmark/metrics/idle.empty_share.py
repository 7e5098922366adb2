"""Of the same slice as `idle.held_share`, the share in which the device was
idle and no request was in the server, %: the traffic's own.  100 − held −
empty is the busy share, over a denominator that holds no `stop_trace()`."""
from pio_bench import hostjoin


def read(ctx):
    return hostjoin.idle_share(ctx, "empty_s")
