"""Published peaks of one chip, keyed by a substring of JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
HBM, 16 GB) and the matching pages of the other generations.  A device
that is not in the table is an error, never a default: a utilization
computed against the wrong peak is worse than none.
"""
from __future__ import annotations

# (substring of device_kind.lower(), bf16 FLOP/s, HBM bytes/s)
PEAKS = (
    ("v6", 918e12, 1640e9), ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5 lite", 197e12, 819e9), ("v5e", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
)


def peaks_for(device_kind: str) -> dict:
    """`{"flops": bf16 FLOP/s, "hbm_bytes": bytes/s}` of one chip."""
    kind = device_kind.lower()
    for sub, flops, hbm in PEAKS:
        if sub in kind:
            return {"flops": flops, "hbm_bytes": hbm}
    raise ValueError(f"unknown device_kind {device_kind!r}: add its published "
                     "peaks to benchmarks/lib/peaks.py before reporting a "
                     "utilization or a roofline share")
