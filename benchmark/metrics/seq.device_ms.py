"""Device duration of the sequence programs (`pio_seq_forward`: trunk, head
and top-k of one packed dispatch) in the traced slice over their executions,
ms per dispatch (profiler trace)."""
from pio_bench.xplane_named import program_seconds


def read(ctx):
    seconds, count = program_seconds(ctx)
    return 1e3 * seconds / count if count else None
