"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Copied from the system's ``launch/roofline.PEAKS``.  Source: Google
Cloud TPU documentation, "TPU v5e" system architecture: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.  A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float    # FLOP/s per chip
    hbm_bytes: float     # bytes of HBM per chip
    hbm_bw: float        # HBM bytes/s per chip
    ici_bw: float        # inter-chip bytes/s per chip


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=16e9, hbm_bw=819e9,
                         ici_bw=200e9),
}


def lookup(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
