"""Published per-chip peaks, keyed by jax's ``device_kind`` — the one table
roofline shares are taken against. A copy of
``benchmarks/attribution.py: DEVICE_PEAKS`` (kept here so that no later PR
can move the yardstick). An accelerator that is not in the table is an
error, never a default; the CPU platform has no device peaks, and nothing
timed there is a device metric.
"""

DEVICE_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(platform: str, device_kind: str) -> dict | None:
    """Peaks of the device; None on the CPU platform, KeyError for an
    accelerator the table lacks."""
    if platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"chipbench/peaks.py DEVICE_PEAKS with its source") from None
