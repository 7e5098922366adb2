"""Least time the chip could take for the state-space recurrence of the
traced slice's OWN dispatches (costs_ssd.state_space_scan: 5 x head size x
state flops a token a head — 5 x 64 x 128 over 128 heads in ONE group here;
x read and y written once a head, B and C once a group, dt as f32; whatever
chunking or head tiling implements it, so a chunk's C B^T made once a grid
step of 16 heads and not once a group reads as a LOW share, never over 100;
at the real tokens of the histories that rode the slice's dispatches,
`wattn.slice_work`, times the layers that scan: the `mamba` entries of this
stage's `layer_types`, nine of ten) over the device time of the ops named
`ssd_scan` in the slice, %.  A dispatch cut by the slice's edge is in the
time and not in the work: the share can read low by it, never high.  A
program without the op, or a configuration without a stage's layer kinds,
gives nothing to read."""
from pio_bench import costs_ssd
from pio_bench.wattn import slice_work
from pio_bench.xplane_named import op_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "ssd_scan")
    cfg = ctx["cfg"]
    if not seconds or "stage" not in cfg or "layer_types" not in cfg:
        return None
    work = slice_work(ctx, None)
    first = cfg["stage"]["first_layer"]
    layers = cfg["layer_types"][
        first:first + cfg["num_hidden_layers"]].count("mamba")
    if work is None or not layers:
        return None
    cost = costs_ssd.state_space_scan(
        layers * work[1], 0, cfg["mamba_n_heads"], cfg["mamba_n_groups"],
        cfg["mamba_d_head"], cfg["mamba_d_state"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least / seconds
