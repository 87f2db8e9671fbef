"""Published peaks of the chips the benchmark runs on, keyed by device_kind.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
A device kind that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
