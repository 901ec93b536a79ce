"""Published peaks of one chip, keyed by ``jax.Device.device_kind``."""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    hbm_bytes: float
    source: str

    def bound_s(self, flops: float, byts: float) -> float:
        """The least time the chip could take for this work."""
        return max(flops / self.flops, byts / self.hbm_bw)


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks_for(kind: str) -> Peaks:
    """A device that is not in the table is an error, not a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
