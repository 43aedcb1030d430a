"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. Source: Google Cloud documentation, "TPU v5e" (per
chip: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def for_device(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
