"""Least time the chip could take for the full layers' attention of the
traced slice's dispatches (costs_gdn.causal_attention at the window's mean
causal pairs and tokens per dispatch) over the device time of
`pio.packed_attention`, %."""
from pio_bench import costs_gdn
from pio_bench.xplane_named import op_seconds, per_dispatch, program_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "packed_attention")
    _, count = program_seconds(ctx)
    pairs = per_dispatch(ctx, "fastpath.causal_pairs")
    tokens = per_dispatch(ctx, "fastpath.tokens")
    if not seconds or not count or pairs is None or tokens is None:
        return None
    cfg = ctx["cfg"]
    # the file holds the published pattern whole; the cut runs its first layers
    full = cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")
    cost = costs_gdn.causal_attention(
        pairs, tokens, full, cfg["num_attention_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least * count / seconds
