"""Peak rates of one chip, keyed by ``jax.Device.device_kind``.

Copied from ``repro.launch.mesh.CHIP_PEAKS``.  Source: Google Cloud
documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16, 393
TOP/s int8, 16 GB of HBM at 819 GB/s.  A device kind that is not here is
an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
