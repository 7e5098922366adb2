"""Of the traced slice between the first and the last device op, the share
in which the device was idle AND some `pio_req.*` span was open, %: what the
host holds the chip back by under this traffic.  A program that marks no
request presence reads nothing (`pio_bench/hostjoin.py`)."""
from pio_bench import hostjoin


def read(ctx):
    return hostjoin.idle_share(ctx, "held_s")
