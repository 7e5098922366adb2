"""Of the sequence programs' device time in the traced slice, the share of
the attention kernels of the window/global family (`pio.window_attention` +
`pio.global_attention`: one Pallas op a layer), %.  The projections, the
per-head norms, the rotary embedding and the gate around them are XLA's and
are not in it."""
from pio_bench.xplane_named import op_seconds, program_seconds


def read(ctx):
    total, _ = program_seconds(ctx)
    window, _ = op_seconds(ctx, "window_attention")
    if not total or window is None:
        return None
    glob, _ = op_seconds(ctx, "global_attention")
    return 100.0 * (window + (glob or 0.0)) / total
