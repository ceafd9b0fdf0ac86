"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 and 16 GB of HBM
at 819 GB/s per chip. A chip not in the table is an error."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
