"""Token tiles the sequence program's position-wise sublayers RAN over the
tiles of the rungs they ran in (`dense_tiles` / `dense_tiles_rung`, from the
packed scorer's counters over the window), %: 100 = every dispatch ran its
whole rung; under it, a rung's padded tail cost the projections and the
feed-forward nothing.  A rung of two tiles or fewer is run whole and counts as one tile, run.  A
program without the counters (the parent of ISSUE 42, or a family that does
not run in tiles) gives nothing to read."""
from pio_bench.readers import delta


def read(ctx):
    ran, rung = (delta(ctx, "fastpath.dense_tiles"),
                 delta(ctx, "fastpath.dense_tiles_rung"))
    if ran is None or not rung:
        return None
    return 100.0 * ran / rung
