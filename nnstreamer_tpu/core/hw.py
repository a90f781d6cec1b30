"""Accelerator detection (hw_accel.c:42-64 equivalent, TPU-first).

The reference probes NEON via getauxval; ours probes the PJRT platform set
through JAX. Results cached process-wide; safe to call before/without TPU.
Also hosts the accelerator-string parser (parse_accl_hw,
nnstreamer_plugin_api_filter.h:547-568): strings like
"true:tpu", "false", "true:cpu,tpu" pick execution devices — and the
one place the persistent compilation cache is pointed somewhere.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import List, Optional, Tuple

from .log import logger

#: the directory that holds the ``nnstreamer_tpu`` package (the checkout)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile (``nns-launch``, ``chip_smoke.py``).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    is set here. Unset: ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of the cache key and a directory that moves (a
    hostname, a pid, a tempdir in its name) never hits. Returns the
    directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.lru_cache(maxsize=None)
def available_platforms() -> Tuple[str, ...]:
    import jax

    plats = []
    for name in ("tpu", "gpu", "cpu"):
        try:
            if jax.devices(name):
                plats.append(name)
        except RuntimeError:  # platform not present in this process
            continue
    return tuple(plats)


def on_tpu(device) -> bool:
    """True when ``device`` (a ``jax.Device``) is a TPU.

    Host-side decisions only (placement checks, the chip smoke's gate).
    Code running under ``jit`` cannot see its device: the Pallas entry
    points in ``ops/pallas`` select per lowering platform through
    ``jax.lax.platform_dependent`` instead."""
    return device.platform == "tpu"


def default_device():
    import jax

    return jax.devices()[0]


@dataclass(frozen=True)
class AcceleratorSpec:
    """Parsed ``accelerator=`` property value."""

    enabled: bool = True
    preference: Tuple[str, ...] = ()  # ordered platform names, e.g. ("tpu","cpu")

    @classmethod
    def parse(cls, value: Optional[str]) -> "AcceleratorSpec":
        if not value:
            return cls(True, ())
        s = str(value).strip().lower()
        if ":" in s:
            flag, prefs = s.split(":", 1)
        else:
            flag, prefs = s, ""
        enabled = flag in ("true", "1", "yes", "on", "auto", "")
        preference = tuple(p.strip() for p in prefs.split(",") if p.strip())
        return cls(enabled, preference)

    def pick_device(self):
        """Resolve to a concrete jax.Device honoring preference order.

        An unavailable preference falls through to the process default
        device (reference parse_accl_hw semantics) — with a warning, so a
        ``true:tpu`` pipeline that landed on a CPU says so."""
        import jax

        if not self.enabled:
            try:
                return jax.devices("cpu")[0]
            except RuntimeError:
                return jax.devices()[0]
        for plat in self.preference:
            try:
                devs = jax.devices(plat)
                if devs:
                    return devs[0]
            except RuntimeError:
                continue
        dev = jax.devices()[0]
        if self.preference:
            logger("hw").warning(
                "accelerator preference %s not available; placing on %s",
                ",".join(self.preference), dev)
        return dev
