"""Least time the chip could take for every layer's attention of the traced
slice's OWN dispatches (costs_wmoe.windowed_attention at the causal pairs of
the histories that rode them, `wattn.slice_work(ctx, None)`, times the
layers: every layer of this family attends, 20 query heads over 4 key/value
heads of 128) over the device time of `pio.global_attention` in the slice,
%.  Pairs go with the square of a length, so the window's mean held against
a slice's time can pass 100 % (PERF.md section 6, PR 39)."""
from pio_bench import costs_wmoe
from pio_bench.wattn import slice_work
from pio_bench.xplane_named import op_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "global_attention")
    if not seconds:
        return None
    work = slice_work(ctx, None)
    if work is None:
        return None
    cfg = ctx["cfg"]
    layers = cfg["num_hidden_layers"]
    cost = costs_wmoe.windowed_attention(
        layers * work[0], work[1], layers, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least / seconds
