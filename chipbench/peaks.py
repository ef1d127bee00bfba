"""Published peaks per device kind, as ``jax.devices()[i].device_kind``
names it. A device that is not listed is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
