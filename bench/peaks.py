"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
of HBM bandwidth, 16 GB of HBM per chip).  The FLOP/s peak is the bf16
one.  The pipeline runs f32 matmuls at HIGHEST precision, which the TPU
computes as several bf16 passes (six for a full f32 product), so a
roofline share taken against this peak sits well under 100% even for a
perfect f32 kernel: about 17% is the ceiling of a HIGHEST matmul.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float
    bytes_per_s: float
    memory_bytes: float


PEAKS = {
    "TPU v5 lite": Peak(flops_per_s=197e12, bytes_per_s=819e9, memory_bytes=16e9),
}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
