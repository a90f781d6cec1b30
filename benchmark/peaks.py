"""The table of peaks, keyed by ``device_kind`` as the chip reports it.

A device that is not in ``peaks.json`` is an error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def load_table() -> Dict[str, Dict[str, float]]:
    with open(_TABLE, encoding="utf-8") as f:
        return json.load(f)["by_device_kind"]


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_table()
    if device_kind not in table:
        raise KeyError(
            f"no peak recorded for device_kind {device_kind!r} "
            f"(benchmark/peaks.json has {sorted(table)})")
    return table[device_kind]
