"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default: a number computed against a guessed peak means nothing."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
