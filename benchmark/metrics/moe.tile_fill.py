"""Real rows over the rows of the work items the grouped products ran
(`expert_assignments` / (`expert_row_tiles` x 128), the packed scorer's
counters over the window), %: a work item is one (128-row tile, held expert)
pair that holds a real row, and it runs the MXU over the whole tile; what
many small groups cost.  A program without the counter (this family's
parent, or a family that does not count its work items) gives nothing to
read."""
from pio_bench.readers import delta

ROW_TILE = 128


def read(ctx):
    rows, tiles = (delta(ctx, "fastpath.expert_assignments"),
                   delta(ctx, "fastpath.expert_row_tiles"))
    if rows is None or not tiles:
        return None
    return 100.0 * rows / (tiles * ROW_TILE)
