"""Least time the chip could take for the attention of the traced slice's
OWN dispatches (costs_wmoe.windowed_attention at the causal pairs of the
histories that rode them, `wattn.slice_work(ctx, None)`, times the layers
that attend: the `attention` entries of this stage's `layer_types`, ONE of
ten; 32 query heads over 8 key/value heads of hidden / heads = 128) over the
device time of `pio.global_attention` in the slice, %.  Pairs go with the
square of a length, so the work is counted for the dispatches that ARE in
the slice (PERF.md section 6, PR 39).  A program without the op, or a
configuration without a stage's layer kinds, gives nothing to read."""
from pio_bench import costs_wmoe
from pio_bench.wattn import slice_work
from pio_bench.xplane_named import op_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "global_attention")
    cfg = ctx["cfg"]
    if not seconds or "stage" not in cfg or "layer_types" not in cfg:
        return None
    work = slice_work(ctx, None)
    first = cfg["stage"]["first_layer"]
    layers = cfg["layer_types"][
        first:first + cfg["num_hidden_layers"]].count("attention")
    if work is None or not layers:
        return None
    cost = costs_wmoe.windowed_attention(
        layers * work[0], work[1], layers, cfg["num_attention_heads"],
        cfg["num_key_value_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least / seconds
