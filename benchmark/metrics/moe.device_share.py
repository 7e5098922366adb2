"""Of the sequence programs' device time in the traced slice, the share of
the grouped expert products (`pio.moe_experts`: three Pallas ops a sparse
layer), %.  Routing, the sort by expert, the gather back and the combine are
XLA fusions, which a TPU trace does not name: they are not in it."""
from pio_bench.xplane_named import op_seconds, program_seconds


def read(ctx):
    total, _ = program_seconds(ctx)
    experts, _ = op_seconds(ctx, "moe_experts")
    if not total or experts is None:
        return None
    return 100.0 * experts / total
