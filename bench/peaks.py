"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s.  A kind missing here is an
error: a roofline share against another chip's peak means nothing.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,       # FLOP/s
        "ops_int8": 393e12,         # OP/s
        "hbm_bw": 819e9,            # B/s
        "hbm_bytes": 16e9,          # B
    },
}


def peaks(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
