"""Published peaks of each accelerator kind, keyed by ``device_kind``.

A device kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 '
               'TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s)'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add them, with their source, to "
            f"bench/peaks.py") from None
