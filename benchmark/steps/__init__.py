"""Which counter counts a configuration's decode step.

``decode_step_ms``, ``decode_mfu`` and ``decode_hbm_roofline`` are one reader
each for every cell, so that every cell reports the whole step's share under
one name. What a step's operations and bytes are depends on the block the
configuration runs: each module of this directory counts one block and names,
in ``KEYS``, the keys of a configuration file it counts from. The counter of
a configuration is the one module whose keys the file has, never chosen by a
cell's or a configuration's name. A later family adds a module here and edits
nothing.

A counter has ``traced(ctx)`` (the traced iterations' device seconds, decode
steps and work, or None where the trace holds nothing of the step),
``step_flops(ctx, got)`` and ``step_bytes(ctx, got)`` (None where the program
lacks a counter it needs).
"""

from __future__ import annotations

import importlib
import pkgutil
from typing import Any, Dict, List

#: the program whose executions are the decode steps, as the trace names it
MODULE = "jit__decode_chunk"


def counters() -> List[Any]:
    return [importlib.import_module(f"{__name__}.{m.name}")
            for m in pkgutil.iter_modules(__path__)]


def missing(mod: Any, config: Dict[str, Any]) -> List[str]:
    return [k for k in mod.KEYS if k not in config]


def counter(config: Dict[str, Any]) -> Any:
    """The one module that counts ``config``'s step; a configuration that
    none or several can count raises, with what each one misses."""
    mods = counters()
    found = [m for m in mods if not missing(m, config)]
    if len(found) == 1:
        return found[0]
    if found:
        raise ValueError(
            "a decode step of this configuration is counted by more than "
            f"one of benchmark/steps: {[m.__name__ for m in found]}")
    lacks = "; ".join(f"{m.__name__.rsplit('.', 1)[1]} lacks "
                      f"{missing(m, config)}" for m in mods)
    raise ValueError(
        "no counter in benchmark/steps counts a decode step of the "
        f"configuration {config.get('name')!r}: {lacks}")
