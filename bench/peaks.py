"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: int           # bytes of device memory
    hbm_bytes_per_s: float   # bytes/s
    source: str


TPU_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16 * 10**9,
                hbm_bytes_per_s=819e9,
                source='Google Cloud documentation, "TPU v5e"')

PEAKS: Dict[str, Peaks] = {"TPU v5 lite": TPU_V5E}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in the table raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
