"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default.

TPU v5e: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
1,600 Gbit/s chip-to-chip interconnect (Google Cloud documentation,
"TPU v5e"). JAX names the chip "TPU v5 lite".
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": 'Google Cloud documentation, "TPU v5e"'}

PEAKS: Dict[str, Dict] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}") \
            from None
