"""The chips' peaks, and the least time a count of work takes on one.

The counts themselves are the block's (``forward_flops`` and
``reorth_needed`` in ``bench/blocks/<block>.py``).  They are computed from
the configuration's sizes and the real (unpadded) request lengths, never
from what a kernel streams today: bucket padding, batch padding and
re-reads lower a roofline share instead of raising the count.
"""
from __future__ import annotations

from typing import Dict, Tuple

#: ``jax.Device.device_kind`` → published per-chip peaks.  A kind that is
#: not here is an error, not a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    pk = peaks(device_kind)
    tf, tb = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
