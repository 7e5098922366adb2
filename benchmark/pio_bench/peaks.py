"""The one table of device peaks, keyed by ``device_kind`` as JAX reports
it.  A device that is not in the table is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture — 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.  An f32
contraction at ``Precision.HIGHEST`` runs as six bf16 passes on the MXU, so
its peak is a sixth of the bf16 figure.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "f32_highest_flops_per_s": 197e12 / 6,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def for_kind(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add a row with its "
            "source to benchmark/pio_bench/peaks.py")
    return PEAKS[device_kind]
