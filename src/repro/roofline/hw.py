"""Peak hardware numbers per TPU generation, keyed by ``device_kind``.

Keys are the exact ``jax.devices()[0].device_kind`` string the chip reports.
The roofline analysis of the production mesh (``roofline.analysis``,
``launch.dryrun``) targets a v5e pod whatever machine lowers it: the module
constants below are that target's.  A comparison of a *measurement* with a
peak (:func:`hbm_fraction`) looks the peaks up by the kind of the device
that measured, and raises for a kind it has no numbers for (a CPU host, an
unlisted TPU) rather than borrow another chip's.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float         # FLOP/s per chip, bf16 matmul
    hbm_bw: float             # B/s per chip
    hbm_bytes: float          # per chip
    ici_bw: float             # B/s per link (~per-chip usable for ring ops)
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9,
        source="Cloud TPU v5e system architecture page: 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s, ICI 1600 Gbps over 4 links"),
}

_TARGET = PEAKS["TPU v5 lite"]   # the chip the production-mesh analysis targets
PEAK_FLOPS_BF16 = _TARGET.flops_bf16
HBM_BW = _TARGET.hbm_bw
ICI_BW = _TARGET.ici_bw
HBM_BYTES = _TARGET.hbm_bytes
CHIPS_PER_POD = 256

# DCI (inter-pod) is far slower than ICI; pod-axis collectives cross it.
DCI_BW = 12.5e9               # B/s per chip, conservative


def peaks(kind: Optional[str] = None) -> ChipPeaks:
    """Peaks of device kind ``kind`` (default: this process's device 0).
    Raises ``KeyError`` for a kind with no listed peaks."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak numbers for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def implied_bandwidth(us_per_byte_equiv: float) -> float:
    """Effective byte-equivalents/second implied by a measured/model
    calibration ratio (the exec cost model is denominated in
    byte-equivalents; ``repro.obs.audit`` produces the ratio in us per
    byte-equivalent)."""
    return 1e6 / max(float(us_per_byte_equiv), 1e-30)


def hbm_fraction(us_per_byte_equiv: float,
                 kind: Optional[str] = None) -> float:
    """:func:`implied_bandwidth` as a fraction of the HBM roofline of the
    device kind that measured it (see :func:`peaks`)."""
    return implied_bandwidth(us_per_byte_equiv) / peaks(kind).hbm_bw
