"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # dense bf16 operations per second
    int8_ops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
             hbm_bytes=16e9,
             source='Google Cloud documentation, "TPU v5e"')

TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
