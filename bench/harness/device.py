"""The device a run measures: the TPU check, its description for the
result line, its peak memory, and the table of published peaks."""
from __future__ import annotations

from typing import Any, Dict


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


#: Published peaks per chip, keyed by JAX's ``device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB HBM at 819 GB/s.  A device that is not here is an error.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "ops_int8": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak_for(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"add them to PEAKS with their source")
    return PEAKS[kind]


def require_tpu(chips: int):
    """The devices of a TPU with at least ``chips`` chips, else
    :class:`NoAccelerator`.  Never falls back to the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoAccelerator(str(e)) from e
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX platform is {devices[0].platform!r}, "
                            f"not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"{len(devices)} TPU chip(s), the cell needs "
                            f"{chips}")
    return devices[:chips]


def describe(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports
    it (0 where it does not, as on the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
