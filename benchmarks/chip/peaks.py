"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``device_kind`` as JAX reports it.  A device that is not in the
table is an error: a roofline or utilization against a guessed peak would
be a number with no meaning.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, bf16 operands on the MXU
    int8_ops: float        # OP/s, int8 operands on the MXU
    hbm_bytes: float       # bytes/s, HBM bandwidth
    hbm_capacity: float    # bytes of HBM per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes=819e9, hbm_capacity=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises KeyError for any other device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
