"""Least time the chip could take for the gated delta rule of the traced
slice's dispatches (costs_gdn.gated_delta_scan: the work of the RECURRENCE
at the window's mean real tokens and rows per dispatch over the linear
layers, whatever chunking implements it) over the device time of
`pio.gdn_scan`, %."""
from pio_bench import costs_gdn
from pio_bench.xplane_named import op_seconds, per_dispatch, program_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "gdn_scan")
    _, count = program_seconds(ctx)
    tokens = per_dispatch(ctx, "fastpath.scan_tokens")
    rows = per_dispatch(ctx, "fastpath.scan_rows")
    if not seconds or not count or tokens is None or rows is None:
        return None
    cfg = ctx["cfg"]
    cost = costs_gdn.gated_delta_scan(
        tokens, rows, cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least * count / seconds
