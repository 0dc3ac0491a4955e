"""Peak numbers per chip, keyed by ``jax.devices()[0].device_kind``.

Copied from the repository's ``roofline/hw.PEAKS`` so that the yardstick
cannot move with the program.  A kind that is not listed raises: a share of
a peak is never computed against another chip's numbers.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,       # FLOP/s per chip, bf16 matmul
        "hbm_bw": 819e9,            # B/s per chip
        "hbm_bytes": 16e9,
        "source": "Cloud TPU v5e system architecture page: 197 TFLOP/s "
                  "bf16, 16 GB HBM2 at 819 GB/s, ICI 1600 Gbps over 4 links",
    },
}


def lookup(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak numbers for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
