"""Device duration of the head's score-and-top-k kernel (`pio.score_topk`,
the op the ALS cells' score program is made of) inside the sequence
programs, ms per dispatch (profiler trace)."""
from pio_bench.xplane_named import op_seconds, program_seconds


def read(ctx):
    seconds, _ = op_seconds(ctx, "score_topk")
    _, count = program_seconds(ctx)
    return 1e3 * seconds / count if seconds and count else None
