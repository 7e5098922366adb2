"""Of the sequence programs' device time in the traced slice, the share of
the linear layers' recurrence: the chunked scan kernel `pio.gdn_scan` (one
Pallas op a linear layer) plus `pio.gdn_conv` where a trace names it (the
program's convolution is an XLA fusion, which a TPU trace does not name:
then the scan alone), %.  The projections, gates and norms around the scan
are XLA's and are not in it."""
from pio_bench.xplane_named import op_seconds, program_seconds


def read(ctx):
    total, _ = program_seconds(ctx)
    scan, _ = op_seconds(ctx, "gdn_scan")
    if not total or scan is None:
        return None
    conv, _ = op_seconds(ctx, "gdn_conv")
    return 100.0 * (scan + (conv or 0.0)) / total
