"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 per chip, 16 GB HBM2 at 819 GB/s.  Every dot of the
system and of the reference runs in f32 at ``Precision.HIGHEST`` (six bf16
passes), so the bf16 peak overstates what f32 work can reach; it is the
yardstick all the same, so shares stay comparable across precisions.
"""
from __future__ import annotations

from typing import Dict

from .manifest import BenchError

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of a device kind; an unknown kind is a :class:`BenchError`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise BenchError(f"device kind {device_kind!r} is not in the peaks "
                         f"table ({sorted(PEAKS)}); add its published peaks "
                         "to bench/peaks.py") from None
