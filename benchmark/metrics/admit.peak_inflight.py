"""The most requests in flight at one instant of the window (sent, not yet
answered): the headroom to the server's admission gate, which sheds with 503
at `max_inflight` (256 by default).  A guard: a run that nears the gate is
one host stall from failed requests."""
from pio_bench.readers import peak_inflight


def read(ctx):
    return peak_inflight(ctx["records"])
