"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  The FLOP peak is the
chip's highest published floating-point rate, so a share of it is a lower
bound.  A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_time_s(work: dict, peak: dict) -> tuple:
    """The least time ``work`` ({"flops", "bytes"}) can take at the peaks,
    and which bound binds ("flops" or "bytes")."""
    tf = work["flops"] / peak["flops_per_s"]
    tb = work["bytes"] / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
