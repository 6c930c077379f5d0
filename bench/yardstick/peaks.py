"""Per-chip peaks of the devices the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip).  A kind missing here
is an error, never a default: a roofline share against a guessed peak
means nothing.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops": 197e12,      # bf16 FLOP/s per chip
        "int8_ops": 393e12,   # int8 OP/s per chip
        "hbm_bw": 819e9,      # HBM bytes/s per chip
        "hbm_bytes": 16e9,    # HBM bytes per chip
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of ``device_kind``; raises ``KeyError`` for a kind
    with no published numbers here."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


def least_time(flops: float, nbytes: float, peak: dict, chips: int):
    """The least seconds ``chips`` chips need for the work, and which
    resource binds (``"flops"`` or ``"hbm"``)."""
    tf = flops / (chips * peak["flops"])
    tb = nbytes / (chips * peak["hbm_bw"])
    return (tf, "flops") if tf >= tb else (tb, "hbm")
