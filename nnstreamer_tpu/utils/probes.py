"""Chip peaks and XLA's own FLOP count.

The peak tables (bf16 MXU FLOP/s, HBM bytes/s) are keyed by
``device_kind``; a device in neither table is an error, not a default.
``model_flops`` asks XLA's compiled-cost analysis for the per-invoke FLOP
count. What a run achieves against the peaks is the benchmark's to say
(``benchmark/``, PERF.md §3), from a device trace.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

#: per-chip peak dense-matmul FLOP/s used for MFU accounting, keyed by a
#: substring of jax device_kind. bf16 MXU numbers (public chip specs).
#: Accelerators only: a device that matches no key has no peak, and
#: asking for one raises (a utilization of a CPU means nothing).
PEAK_FLOPS = {
    "v5 lite": 197e12,  # TPU v5e
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6": 918e12,  # Trillium
}

#: per-chip peak HBM bandwidth (bytes/s), same device_kind keying —
#: the roofline's memory ceiling (public chip specs).
PEAK_HBM_BW = {
    "v5 lite": 819e9,  # TPU v5e
    "v5e": 819e9,
    "v4": 1228e9,
    "v5p": 2765e9,
    "v6": 1640e9,  # Trillium
}


class UnknownDeviceError(LookupError):
    """The device's ``device_kind`` is not in the peak tables."""


def _by_device_kind(table: Dict[str, float], device: Any = None) -> float:
    import jax

    device = device or jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or str(device)).lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise UnknownDeviceError(
        f"no peak recorded for device kind {kind!r} "
        f"(known: {sorted(table)}); add it to utils/probes.py with its "
        "source before reporting a utilization on it")


def chip_peak_flops(device: Any = None) -> float:
    return _by_device_kind(PEAK_FLOPS, device)


def chip_peak_hbm_bw(device: Any = None) -> float:
    return _by_device_kind(PEAK_HBM_BW, device)


def ridge_intensity(device: Any = None) -> float:
    """Roofline ridge point (FLOPs/byte): operational intensity below
    this is memory-bound, above it compute-bound, on this chip."""
    return chip_peak_flops(device) / chip_peak_hbm_bw(device)


def model_flops(fn: Callable, *example_args: Any) -> Optional[float]:
    """Per-invoke FLOPs from XLA's compiled cost analysis (None if the
    backend doesn't expose it)."""
    import jax

    try:
        compiled = jax.jit(fn).lower(*example_args).compile()
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0)) if cost else 0.0
        return flops if flops > 0 else None
    except Exception:
        return None
