"""Real rows a touched HELD expert saw, per sparse layer and dispatch
(`expert_assignments` / `experts_touched`, the packed scorer's counters over
the window), rows.  An expert's three products are bound by its weights'
trip from HBM until it sees ~240 rows (197 TFLOP/s over 819 GB/s at 2
flops a weight byte and row): this says which side of that ridge the
grouped products ran on."""
from pio_bench.readers import delta


def read(ctx):
    rows, touched = (delta(ctx, "fastpath.expert_assignments"),
                     delta(ctx, "fastpath.experts_touched"))
    if rows is None or not touched:
        return None
    return rows / touched
