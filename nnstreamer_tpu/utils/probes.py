"""Performance probes: per-phase H2D/compute/D2H splits, FLOPs, MFU.

The reference exposes per-filter invoke latency / throughput as runtime
props (tensor_filter.c:366-400, tensor_filter_common.c:967-981) but cannot
say *where* an invoke's time goes.  A synchronous per-invoke number
mixes the host↔device round trip with chip time, so these probes measure
each phase the way streaming pipelines actually run it: **pipelined**, K
transfers/invokes in flight, reporting the amortized per-frame cost.  A
separate single synchronous round-trip isolates the round trip itself.

``model_flops`` asks XLA's compiled-cost analysis for the per-invoke FLOP
count; ``mfu`` relates achieved FLOP/s to the chip's peak (bf16 MXU).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

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


def mfu(flops_per_frame: Optional[float], fps: float,
        device: Any = None) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over chip peak. Only an
    *MFU* when fps is measured over device-busy time (a saturating or
    synced loop). For an end-to-end pipeline rate — where batching
    budgets, wire round trips, and host stages sit between frames — use
    ``pipeline_util``, which is the same ratio under its honest name."""
    if not flops_per_frame or not np.isfinite(fps):
        return None
    return flops_per_frame * fps / chip_peak_flops(device)


def pipeline_util(flops_per_frame: Optional[float], fps: float,
                  device: Any = None) -> Optional[float]:
    """Fraction of chip peak consumed by a pipeline running end-to-end
    at ``fps``: (per-frame FLOPs × fps) / peak. Deliberately NOT called
    MFU: wall-clock fps includes everything that is not the chip
    (batch-formation budgets, queue waits, host pre/post, wire RTT), so
    tiny values mean "the chip is mostly idle between frames", not "the
    model runs inefficiently"."""
    return mfu(flops_per_frame, fps, device)


def _pipelined(run_one: Callable[[int], Any], k: int,
               finish: Callable[[Sequence[Any]], None]) -> float:
    """Launch k ops back-to-back, block at the end; per-op seconds."""
    outs = [run_one(i) for i in range(k)]
    finish(outs)
    t0 = time.perf_counter()
    outs = [run_one(i) for i in range(k)]
    finish(outs)
    return (time.perf_counter() - t0) / k


def phase_split(fn: Callable, example: Sequence[np.ndarray],
                device: Any = None, k: int = 32) -> Dict[str, float]:
    """Amortized per-frame cost of each pipeline phase, in µs:

      * ``rtt_us``     — one synchronous tiny-transfer round trip (the
        latency floor any per-frame sync point pays);
      * ``h2d_us``     — pipelined host→device upload of one input frame;
      * ``compute_us`` — pipelined invoke with inputs already resident;
      * ``d2h_us``     — pipelined device→host readback of the outputs
        (async prefetch, then materialize — the decoder's drain path).

    These are throughput costs: what a deep streaming pipeline pays per
    frame, not what a lone blocking call observes.
    """
    import jax

    device = device or jax.devices()[0]
    jitted = jax.jit(fn)
    host_frames = [np.asarray(a) for a in example]

    # warm compile + resident inputs
    resident = [jax.device_put(a, device) for a in host_frames]
    out = jitted(*resident)
    jax.block_until_ready(out)

    # rtt: single sync round trip of a tiny array
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(np.zeros(4, np.float32), device))
        ts.append(time.perf_counter() - t0)
    rtt = float(np.median(ts))

    h2d = _pipelined(
        lambda i: [jax.device_put(a, device) for a in host_frames],
        k, lambda outs: jax.block_until_ready(outs))

    compute = _pipelined(
        lambda i: jitted(*resident),
        k, lambda outs: jax.block_until_ready(outs))

    def read_back(outs):
        flat = []
        for o in outs:
            flat.extend(o if isinstance(o, (tuple, list)) else [o])
        for o in flat:
            try:
                o.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
        for o in flat:
            np.asarray(o)

    d2h = _pipelined(lambda i: jitted(*resident), k, read_back) - compute
    return {
        "rtt_us": round(rtt * 1e6, 1),
        "h2d_us": round(h2d * 1e6, 1),
        "compute_us": round(compute * 1e6, 1),
        "d2h_us": round(max(d2h, 0.0) * 1e6, 1),
    }
