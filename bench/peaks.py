"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s. A kind that is not here is an
error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
