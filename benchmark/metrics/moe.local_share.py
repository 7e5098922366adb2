"""Assignments to the experts HELD here over all the assignments the router
made (`expert_assignments` / `routed_assignments`, the packed scorer's
counters over the window), %: the held experts' share of the router's, 12.5
for 32 of 256 under an even load; 100 would mean the layer stopped routing
over the experts it does not hold."""
from pio_bench.readers import delta


def read(ctx):
    local, routed = (delta(ctx, "fastpath.expert_assignments"),
                     delta(ctx, "fastpath.routed_assignments"))
    if local is None or not routed:
        return None
    return 100.0 * local / routed
