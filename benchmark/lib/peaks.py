"""Published peaks of the chips the benchmark may run on, keyed by
``jax.Device.device_kind``. A device that is not here is an error, never a
default: a roofline share against a guessed peak is not a measurement."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,   # bf16 matrix units
        "bytes_per_s": 819e9,    # HBM
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmark/lib/peaks.py with its source") from None
