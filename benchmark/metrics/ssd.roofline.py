"""Least time the chip could take for the state-space recurrence of the
traced slice's OWN dispatches (costs_ssd.state_space_scan: 5 x 128 x 256
flops a token a head; x read and y written once a head, B and C once a
GROUP, dt as f32; whatever chunking implements it; at the real tokens of the
histories that rode the slice's dispatches, `wattn.slice_work`, times the
layers: every layer scans) over the device time of the ops named `ssd_scan`
in the slice, %.  The kernel runs at half of that least time on full
chunks, so the window's mean tokens a dispatch (the scorer's `scan_tokens`)
held against a slice of a dozen heavy-tailed dispatches could pass 100 %:
the work is counted for the dispatches that ARE in the slice (PERF.md
section 6, PRs 39 and 41); one cut by the slice's edge is in the time and
not in the work, so the share can read low by it, never high."""
from pio_bench import costs_ssd
from pio_bench.wattn import slice_work
from pio_bench.xplane_named import op_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "ssd_scan")
    if not seconds:
        return None
    work = slice_work(ctx, None)
    if work is None:
        return None
    cfg = ctx["cfg"]
    cost = costs_ssd.state_space_scan(
        cfg["num_hidden_layers"] * work[1], 0, cfg["mamba_n_heads"],
        cfg["mamba_n_groups"], cfg["mamba_d_head"], cfg["mamba_d_state"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least / seconds
