"""Peaks of the chips this benchmark knows, keyed by `device_kind` as JAX
reports it. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
  if device_kind not in PEAKS:
    raise KeyError(
        f"no peaks for device kind {device_kind!r}; the table has "
        f"{sorted(PEAKS)}. Add the chip with its source; there is no default.")
  return PEAKS[device_kind]
