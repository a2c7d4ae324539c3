"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A kind that is not here is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM2e at 819 GB/s.  The v5e reports itself as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
    # Rehearsals only (--rehearse cpu): not a device, and no number read
    # against it is ever written down as a device's.
    "cpu": {
        "bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 50e9,
        "source": "made up for CPU rehearsals",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}: add a row to chipbench/peaks.py"
        ) from None
