"""Least time the chip could take for the expert products of the traced
slice's dispatches (costs_seq.expert_products at the window's mean
assignments and DISTINCT experts touched per dispatch, by peaks.py) over
the device time of `pio.moe_experts`, %."""
from pio_bench import costs_seq
from pio_bench.xplane_named import op_seconds, per_dispatch, program_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "moe_experts")
    _, count = program_seconds(ctx)
    assignments = per_dispatch(ctx, "fastpath.expert_assignments")
    touched = per_dispatch(ctx, "fastpath.experts_touched")
    if not seconds or not count or assignments is None:
        return None
    cfg = ctx["cfg"]
    cost = costs_seq.expert_products(
        assignments, touched, cfg["hidden_size"], cfg["moe_intermediate_size"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least * count / seconds
