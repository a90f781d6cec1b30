"""From a profiler trace to numbers: the one reduction every PR shares.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
plain event list (``jax.profiler.ProfileData``, nothing else):

    {"devices": {"/device:TPU:0": {"ops":     [[name, start_ns, dur_ns], ..],
                                   "modules": [[name, start_ns, dur_ns], ..]}},
     "host": [[name, start_ns, dur_ns], ..]}      # the harness's own spans

``reduce_events`` turns that list into busy time, idle share, per-module
and per-op device time, and idle gaps labelled by what the host was doing.
A trimmed event list recorded on the chip is under ``tests/data`` and
``tests/test_trace_reduce.py`` checks the reduction on it.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed HLO op and ``XLA Modules`` one per
executed program. Host spans are the ``bench.*`` events that
``jax.profiler.TraceAnnotation`` wrote, whatever thread line they are on.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
#: ops that only contain other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")

Event = List[Any]  # [name, start_ns, dur_ns]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend([short_op(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for dev in devices.values():
        dev["ops"].sort(key=lambda e: e[1])
        dev["modules"].sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


_TARGET = 'custom_call_target="'


def short_op(text: str) -> str:
    """The trace names an op by its whole HLO line. Keep the instruction's
    name, and for a custom call its target: ``%branch_0_fun.9 = (s8[..])
    custom-call(..), custom_call_target="tpu_custom_call", ..`` becomes
    ``branch_0_fun.9 [tpu_custom_call]`` (a Pallas kernel)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    i = text.find(_TARGET)
    if i >= 0:
        j = text.find('"', i + len(_TARGET))
        name += f" [{text[i + len(_TARGET):j]}]"
    return name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _short(name: str) -> str:
    """``jit__decode_chunk(123)`` -> ``jit__decode_chunk``."""
    return name.split("(", 1)[0]


def _kind(op: str) -> str:
    """``fusion.2365`` -> ``fusion``; ``branch_0_fun.9 [tpu_custom_call]``
    -> ``branch_0_fun [tpu_custom_call]``."""
    name, _, target = op.partition(" ")
    head, dot, tail = name.rpartition(".")
    if dot and tail.isdigit():
        name = head
    return f"{name} {target}" if target else name


def _is_container(op: str) -> bool:
    base = op.split(".", 1)[0].split(" ", 1)[0]
    return base in CONTAINERS


@dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0                       # mean over the device planes
    n_devices: int = 0
    modules: Dict[str, float] = field(default_factory=dict)   # name -> s
    module_runs: Dict[str, int] = field(default_factory=dict)
    ops: Dict[Tuple[str, str], float] = field(default_factory=dict)
    gaps: Dict[str, float] = field(default_factory=dict)      # label -> s

    def module_seconds(self, prefix: str) -> float:
        """Device seconds of the programs whose name starts with
        ``prefix``, summed over executions, averaged over devices."""
        return sum(s for n, s in self.modules.items()
                   if n.startswith(prefix)) / max(self.n_devices, 1)

    def op_seconds(self, needle: str) -> float:
        """Device seconds of the ops whose name contains ``needle``."""
        return sum(s for (_, op), s in self.ops.items()
                   if needle in op) / max(self.n_devices, 1)

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        """The ten heaviest kinds of device op and the ten longest kinds
        of idle gap. A decode chunk is 24 unrolled layers of numbered
        fusions, none of them heavy alone, so ops are summed by module
        and by name without its number (``fusion.2365`` -> ``fusion``)."""
        kinds: Dict[str, float] = {}
        for (m, o), s in self.ops.items():
            if not _is_container(o):
                key = f"{m}/{_kind(o)}"
                kinds[key] = kinds.get(key, 0.0) + s
        dev = max(self.n_devices, 1)
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s / dev] for n, s in ops],
                "idle_gaps": [[n, s / dev] for n, s in gaps]}


def reduce_events(events: Dict[str, Any]) -> Reduced:
    host = events.get("host", [])
    devices = events.get("devices", {})
    edges = [e[1] for e in host] + [e[1] + e[2] for e in host]
    for dev in devices.values():
        for e in dev["ops"]:
            edges += [e[1], e[1] + e[2]]
    red = Reduced(n_devices=len(devices))
    if not edges or not devices:
        return red
    t0, t1 = min(edges), max(edges)
    red.window_s = (t1 - t0) / 1e9
    host_starts = [e[1] for e in host]
    busy_total = 0.0
    for dev in devices.values():
        mods = dev["modules"]
        mod_starts = [m[1] for m in mods]
        for name, _, dur in mods:
            key = _short(name)
            red.modules[key] = red.modules.get(key, 0.0) + dur / 1e9
            red.module_runs[key] = red.module_runs.get(key, 0) + 1

        def module_at(t: float) -> str:
            i = bisect.bisect_right(mod_starts, t) - 1
            if i >= 0 and t < mods[i][1] + mods[i][2]:
                return _short(mods[i][0])
            return ""

        for name, start, dur in dev["ops"]:
            key = (module_at(start), name)
            red.ops[key] = red.ops.get(key, 0.0) + dur / 1e9
        busy = _union([(e[1], e[1] + e[2]) for e in dev["ops"]
                       if not _is_container(e[0])])
        busy_total += sum(b - a for a, b in busy) / 1e9
        # idle gaps, labelled by the harness span that covers their middle
        # and by the program that runs next (or that they lie inside)
        cuts = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(cuts[0::2], cuts[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2.0
            i = bisect.bisect_right(host_starts, mid) - 1
            what = "outside the harness's spans"
            for k in range(max(i - 2, 0), i + 1)[::-1]:
                if mid < host[k][1] + host[k][2]:
                    what = host[k][0]
                    break
            inside = module_at(mid)
            j = bisect.bisect_left(mod_starts, b - 1.0)
            nxt = _short(mods[j][0]) if j < len(mods) else "end of trace"
            label = f"{what} | in {inside}" if inside \
                else f"{what} | before {nxt}"
            red.gaps[label] = red.gaps.get(label, 0.0) + (b - a) / 1e9
    red.busy_s = busy_total / len(devices)
    return red


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce_events(load_xplane(find_xplane(trace_dir)))
