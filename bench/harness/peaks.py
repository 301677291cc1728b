"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  No integer
rate of the vector unit is published, so a kernel's compute bound cannot
be taken from this table; the uint32 kernels' shares are of the HBM bound
alone.  A device kind that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops=197e12,
                        int8_ops=393e12, hbm_bytes=16e9),
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no published {what} for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
