"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device that is not in the table is an
error: a roofline share against a guessed peak means nothing.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  The
vector unit, which runs the sort's compare-exchange network, has no
published peak, so no compute bound is asserted for the sort kernels.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float        # FLOP/s per chip
    hbm_bytes_per_s: float   # bytes/s per chip
    hbm_bytes: float         # device memory per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "Google Cloud documentation, TPU v5e"),
}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; raises KeyError for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
