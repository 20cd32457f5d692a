"""Published peak rates of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Copied from the program's ``core/devices.py`` so that the yardstick does not
move when a program file does. Source of the ``TPU v5 lite`` (v5e) row:
Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16, 16 GiB HBM at
819 GB/s. A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s, dense bf16 matmul
    hbm_bytes: int         # device memory
    hbm_bw: float          # bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes=16 * 2**30, hbm_bw=819e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time(flops: float, nbytes: float, p: Peaks) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / p.flops_bf16, nbytes / p.hbm_bw)
