"""Of the seconds in which no operation ran on the device between the traced
slice's first and last, the share a `pio.*` host span (a stage the program
names on the profiler's clock) overlaps, %.  Over every idle gap, not the ten
longest.  A program that writes no such span reads nothing."""
from pio_bench import xplane


def _overlap(gaps, spans):
    """Seconds of the sorted disjoint `gaps` that the sorted disjoint
    `spans` cover (both [(start, end)] in ns)."""
    total, j = 0.0, 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < g1:
            total += min(spans[k][1], g1) - max(spans[k][0], g0)
            k += 1
    return total


def named_share(planes):
    named = [(s, d) for name, lines in planes.items()
             if name.startswith("/host:")
             for events in lines.values()
             for n, s, d in events if n.startswith("pio.") and d > 0]
    if not named:
        return None
    dev = sorted(n for n in planes if n.startswith(xplane.DEVICE_PREFIX))
    if dev:
        lines = planes[dev[0]]
        ops = lines.get(xplane.OPS_LINE) or lines.get(xplane.MODULES_LINE)
    else:  # a CPU rehearsal: what the host ran, other than the spans
        ops = [e for name, lines in planes.items() if name.startswith("/host:")
               for events in lines.values()
               for e in events if not e[0].startswith("pio.")]
    _, busy = xplane.union_seconds((s, d) for _, s, d in ops)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if not idle:
        return None
    _, spans = xplane.union_seconds(named)
    return 100.0 * _overlap(gaps, spans) / idle


def read(ctx):
    trace_dir = ctx["device_trace"].get("trace_dir")
    if not trace_dir:
        return None
    return named_share(xplane.load(xplane.find(trace_dir)))
