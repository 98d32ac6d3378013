"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``. A kind that is not here is an error: a share of
a peak is never computed against a guessed or default peak."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float  # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip: 197 TFLOP/s "
               "bf16, 16 GB HBM2 at 819 GB/s)"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
