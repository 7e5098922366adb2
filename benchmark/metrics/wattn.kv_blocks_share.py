"""Key blocks the window layers' sweep ran over the key blocks a sweep to
each history's start would run (`window_kv_blocks` /
`window_kv_blocks_unskipped`, the packed scorer's counters over the window,
over the query blocks that hold a real token), %: 100 means the window hid
nothing of this traffic, or the skip fell out."""
from pio_bench.readers import delta


def read(ctx):
    ran, unskipped = (delta(ctx, "fastpath.window_kv_blocks"),
                      delta(ctx, "fastpath.window_kv_blocks_unskipped"))
    if ran is None or not unskipped:
        return None
    return 100.0 * ran / unskipped
