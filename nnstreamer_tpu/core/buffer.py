"""Stream buffers: N tensor memories + timestamps.

Equivalent of GstBuffer carrying N GstMemory chunks of tensors
(``GstTensorMemory`` tensor_typedef.h:223-227) — but TPU-first: a tensor
memory may be **host** (numpy) or **device** (``jax.Array`` resident in HBM).
Device residency is preserved as buffers flow element-to-element so a
converter→transform→filter chain does exactly one H2D transfer (the reference
pays a CPU<->accelerator copy per filter; cf. tensorrt.cc:212,390
cudaMallocManaged). Conversion happens lazily via ``.host()`` / ``.device()``.

Timestamps are nanoseconds (GStreamer clock-time convention).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Any, List, Optional, Sequence, Tuple, Union

import numpy as np

from .types import TensorInfo, TensorsConfig, TensorsInfo, TensorFormat, TensorDType

NS_PER_SEC = 1_000_000_000
CLOCK_NONE: Optional[int] = None


def _is_jax_array(x: Any) -> bool:
    # cheap check without importing jax at module load
    return type(x).__module__.startswith("jax") or hasattr(x, "addressable_shards")


class TensorMemory:
    """One tensor's storage; host numpy array and/or device jax.Array.

    Exactly one of the two is authoritative at creation; the other view is
    materialized lazily and cached. Mutation is not supported — streaming
    buffers are value-semantic (matches GstBuffer writability rules without
    the refcount dance).
    """

    __slots__ = ("_host", "_device", "_prefetched", "info")

    def __init__(self, array: Any, info: Optional[TensorInfo] = None):
        self._prefetched = False
        if _is_jax_array(array):
            self._device = array
            self._host = None
        else:
            arr = np.asarray(array)
            self._host = arr
            self._device = None
        if info is None:
            src = self._device if self._device is not None else self._host
            shape = src.shape if src.ndim else (1,)
            info = TensorInfo.from_shape(shape, np.dtype(str(src.dtype)))
        self.info = info

    # -- views -------------------------------------------------------------- #
    def host(self) -> np.ndarray:
        """Host numpy view (D2H copy on first access for device tensors)."""
        if self._host is None:
            self._host = np.asarray(self._device)
        return self._host

    def prefetch(self) -> None:
        """Start an async D2H copy so a later ``host()`` is (nearly) free.

        TPU-first pipelining: device→host readback has latency; issuing
        the copy at dispatch time and materializing a few frames later keeps
        many transfers in flight (see tensor_decoder ``async_depth``).
        No-op for host tensors or if already materialized.
        """
        if self._host is None and self._device is not None and not self._prefetched:
            try:
                self._device.copy_to_host_async()
            except (AttributeError, RuntimeError):
                return  # no async copy issued: keep device-side decode paths
            self._prefetched = True

    @property
    def prefetched(self) -> bool:
        return self._prefetched

    def is_ready(self) -> bool:
        """Non-blocking, best-effort: True when ``host()`` is expected not
        to block. Exact for host tensors; for device tensors it reports the
        array's value being available (``jax.Array.is_ready``) — a
        ``prefetch()``ed D2H copy issued at dispatch time has then either
        landed or is in its final leg, so a subsequent ``host()`` is free
        or blocks only for the copy remainder. Lets pipelined consumers
        drain completed frames instead of stalling on the readback."""
        if self._host is not None or self._device is None:
            return True
        try:
            return bool(self._device.is_ready())
        except (AttributeError, RuntimeError):
            return True  # no readiness API: treat as ready (host() blocks)

    def device(self, device: Any = None) -> Any:
        """Device jax.Array (H2D transfer on first access for host tensors)."""
        if self._device is None:
            import jax

            self._device = jax.device_put(self._host, device)
        return self._device

    @property
    def is_device(self) -> bool:
        return self._device is not None

    @property
    def nbytes(self) -> int:
        return self.info.size_bytes

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.info.shape

    @property
    def dtype(self) -> TensorDType:
        return self.info.dtype

    def tobytes(self) -> bytes:
        return np.ascontiguousarray(self.host()).tobytes()

    @classmethod
    def from_bytes(cls, data: bytes, info: TensorInfo) -> "TensorMemory":
        arr = np.frombuffer(bytearray(data), dtype=info.dtype.np_dtype).reshape(info.shape)
        return cls(arr, info)

    def __repr__(self) -> str:
        loc = "device" if self.is_device else "host"
        return f"TensorMemory({self.info.dim_string}:{self.info.dtype}@{loc})"


@dataclass
class Buffer:
    """A frame flowing through the pipeline: up to 16 tensor memories with
    PTS/DTS/duration in ns. ``config`` snapshots negotiated stream config."""

    memories: List[TensorMemory]
    pts: Optional[int] = None
    dts: Optional[int] = None
    duration: Optional[int] = None
    offset: Optional[int] = None  # frame counter
    config: Optional[TensorsConfig] = None
    meta: dict = field(default_factory=dict)  # extensible per-buffer metadata

    # -- construction ------------------------------------------------------- #
    @classmethod
    def from_arrays(cls, arrays: Sequence[Any], pts: Optional[int] = None,
                    duration: Optional[int] = None, **kw: Any) -> "Buffer":
        return cls([a if isinstance(a, TensorMemory) else TensorMemory(a) for a in arrays],
                   pts=pts, duration=duration, **kw)

    @classmethod
    def of(cls, *arrays: Any, **kw: Any) -> "Buffer":
        return cls.from_arrays(arrays, **kw)

    # -- access ------------------------------------------------------------- #
    @property
    def num_tensors(self) -> int:
        return len(self.memories)

    def __len__(self) -> int:
        return len(self.memories)

    def __getitem__(self, i: int) -> TensorMemory:
        return self.memories[i]

    def arrays_host(self) -> List[np.ndarray]:
        return [m.host() for m in self.memories]

    def arrays_device(self) -> List[Any]:
        return [m.device() for m in self.memories]

    @property
    def tensors_info(self) -> TensorsInfo:
        if self.config is not None and self.config.info.format is TensorFormat.STATIC \
                and len(self.config.info) == len(self.memories):
            return self.config.info
        return TensorsInfo(tuple(m.info for m in self.memories)) if self.memories else \
            TensorsInfo((), TensorFormat.FLEXIBLE)

    def with_memories(self, memories: Sequence[TensorMemory],
                      config: Optional[TensorsConfig] = None) -> "Buffer":
        """New buffer with same timestamps but different payload."""
        return Buffer(list(memories), pts=self.pts, dts=self.dts,
                      duration=self.duration, offset=self.offset,
                      config=config, meta=dict(self.meta))

    def copy_meta_from(self, other: "Buffer") -> "Buffer":
        self.pts, self.dts = other.pts, other.dts
        self.duration, self.offset = other.duration, other.offset
        self.meta.update(other.meta)
        return self

    def __repr__(self) -> str:
        t = "none" if self.pts is None else f"{self.pts/1e9:.6f}s"
        return f"Buffer(pts={t}, {self.memories!r})"


def now_ns() -> int:
    return time.monotonic_ns()
