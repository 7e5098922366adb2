"""Least time the chip could take for the traced slice's dispatches (by
benchmark/pio_bench/costs.py and peaks.py) over the device time of the score
programs, %.  The dispatches are priced at the rungs the window hit, in the
window's proportions (bucket_hits deltas)."""
from pio_bench.readers import delta, score_program_seconds


def read(ctx):
    seconds, count = score_program_seconds(ctx)
    hits = delta(ctx, "fastpath.bucket_hits", sub=True)
    if not count or not hits or not sum(hits.values()):
        return None
    cfg, costs, peaks = ctx["cfg"], ctx["costs"], ctx["peaks"]
    total = sum(hits.values())
    least = 0.0
    for rung, n in hits.items():
        c = costs.score_topk(int(rung), cfg["items"], cfg["rank"],
                             cfg["max_k"])
        least += n / total * costs.least_seconds(
            c, peaks, "f32_highest_flops_per_s")[0]
    return 100.0 * least * count / seconds
