"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  A device that is
not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # bf16 FLOP/s
    hbm_bytes: float  # HBM bytes/s


_V5E = Peaks(flops=197e12, hbm_bytes=819e9)

PEAKS: dict[str, Peaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None

