"""Device duration of the score programs in the traced slice over their
executions, ms per dispatch (profiler trace)."""
from pio_bench.readers import score_program_seconds


def read(ctx):
    seconds, count = score_program_seconds(ctx)
    return 1e3 * seconds / count if count else None
