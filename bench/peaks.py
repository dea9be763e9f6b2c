"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a
roofline share against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

from typing import Dict

#: device_kind -> peaks of ONE chip
PEAKS: Dict[str, Dict[str, float | str]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> Dict[str, float | str]:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip the
    table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
