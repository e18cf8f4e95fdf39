"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not in the table is an error: a share of a
peak is never computed against a guessed one."""
from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e" (system architecture page): per
#: chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM2 at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add them to "
            f"bench/peaks.py with their source"
        ) from None
