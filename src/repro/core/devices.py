"""Per-chip peak rates and memory, keyed by ``jax.Device.device_kind``.

One table for every consumer: the planner's memory budget (``core.plans``,
``core.passes.memory``), the roofline model (``launch.roofline``) and the chip
smoke run, which checks the entry against the device that is present. A kind
that is not in the table is an error, never a default.

Source of the ``TPU v5 lite`` (v5e) row: Google Cloud documentation,
"TPU v5e" — 197 TFLOP/s bf16, 16 GiB HBM at 819 GB/s,
1,600 Gbit/s inter-chip interconnect (four links, 50 GB/s each).
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float       # FLOP/s, dense bf16 matmul
    hbm_bytes: int          # device memory
    hbm_bw: float           # bytes/s
    link_bw: float          # bytes/s per inter-chip link


PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bytes=16 * 2**30,
                               hbm_bw=819e9, link_bw=50e9),
}

# the chip the planner sizes programs for when it analyses a mesh it does not
# hold (dry runs, roofline terms); a run on a device reads its own row
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_KIND) -> DevicePeaks:
    """The table row for ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}: add "
                       f"a row with its source to core.devices.PEAKS") from None
