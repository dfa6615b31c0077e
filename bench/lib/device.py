"""The chip a run measures: identity, published peaks and memory.

The benchmark measures accelerators only. :func:`require_chips` refuses a
run whose JAX backend is not a TPU, or that sees fewer chips than the cell
asks for, before anything is built.
"""

from __future__ import annotations

import jax

#: Published per-chip peaks keyed by ``device_kind`` as JAX reports it.
#: TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A kind missing here is an error.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class NoChipError(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"device kind {kind!r} has no published peaks in "
                       f"bench/lib/device.py (known: {sorted(PEAKS)})")
    return PEAKS[kind]


def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChipError`."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChipError(f"JAX found no backend: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChipError(f"JAX found {platform!r} devices, not a TPU; the "
                          f"benchmark measures the chip and never falls "
                          f"back")
    if len(devices) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def device_record(devices) -> dict:
    """The ``device`` entry of the result line: what JAX reports, and the
    peak memory on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}
