"""Of the sequence programs' device time in the traced slice, the share of
the state-space recurrence: the chunked scan kernel `pio.ssd_scan` (one
Pallas op a layer; every op whose own name holds `ssd_scan`), %.  The
convolution `pio.ssd_conv` is an XLA fusion, which a TPU trace does not
name: it, the projections, the gate and the norms around the scan are NOT
in it."""
from pio_bench.xplane_named import op_seconds, program_seconds


def read(ctx):
    total, _ = program_seconds(ctx)
    scan, _ = op_seconds(ctx, "ssd_scan")
    if not total or scan is None:
        return None
    return 100.0 * scan / total
