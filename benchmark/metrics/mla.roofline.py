"""Least time the chip could take for the latent-attention kernel of the
traced slice's dispatches (costs_seq.latent_attention at the window's mean
causal pairs and tokens per dispatch) over the device time of
`pio.mla_attention`, %."""
from pio_bench import costs_seq
from pio_bench.xplane_named import op_seconds, per_dispatch, program_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "mla_attention")
    _, count = program_seconds(ctx)
    pairs = per_dispatch(ctx, "fastpath.causal_pairs")
    tokens = per_dispatch(ctx, "fastpath.tokens")
    if not seconds or not count or pairs is None:
        return None
    cfg = ctx["cfg"]
    cost = costs_seq.latent_attention(
        pairs, tokens, cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least * count / seconds
