"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect (ICI) per chip. A copy kept with the benchmark, so that no
change to the program can move the yardstick. A kind that is not here is
an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float       # dense bf16 FLOP/s
    hbm_bytes: float   # HBM bytes/s
    ici_bytes: float   # inter-chip interconnect bytes/s, per chip
    hbm_capacity: float


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes=819e9,
                         ici_bytes=1600e9 / 8, hbm_capacity=16e9),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for a kind not in
    the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to benchmark/peaks.py with its source") from None
