"""Where the device waits, by the engine's own phase: one traced window of
a cell, on the chip, with the ``serving.*`` spans that ``LMEngine`` writes
through the profiler read beside the device's ops.

    python3 benchmark/tools/phases.py --workload <cell> --seed 5550123

The harness's reduction keeps ``bench.*`` host spans only and looks three
spans back for the one that covers a gap, so ``breakdown.idle_gaps`` names
``bench.step_iteration`` and no more (PERF.md §7 has the two edits a
``benchmark`` PR needs). This tool loads the same ``.xplane.pb`` with
``serving.`` kept as well, walks the span stack itself, and splits every
idle interval of the device over the innermost span open at each instant.
Idle time under no ``serving.*`` span is "harness, between steps" (the
harness's submit and stamp, and its loop), but for what lies inside
``bench.step_iteration`` and outside ``serving.step``, which has a row of
its own. One JSON object goes to standard output (last line) and to
``chiprun_out/phases_<cell>.json``; the table before it is for reading.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

HARNESS = "harness, between steps"
STEP_WRAP = "bench.step_iteration, outside serving.step"
PREFIXES = ("bench.", "serving.")

Segment = Tuple[float, float, str]  # start_ns, end_ns, innermost span


def innermost_segments(host: List[List[Any]]) -> List[Segment]:
    """The time some span covers, cut where the innermost open span
    changes: ``host`` is ``[name, start_ns, dur_ns]`` events of one
    thread, nested or side by side in any order."""
    segs: List[Segment] = []
    stack: List[Tuple[str, float]] = []   # (name, end_ns), outermost first
    at = 0.0

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                segs.append((at, end, name))
                at = end

    for name, start, dur in sorted(host, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > at:
            segs.append((at, start, stack[-1][0]))
        at = start
        stack.append((name, start + dur))
    close_until(float("inf"))
    return segs


def label_of(span: str) -> str:
    if span.startswith("serving."):
        return span
    return STEP_WRAP if span == "bench.step_iteration" else HARNESS


def idle_by_phase(events: Dict[str, Any]) -> Dict[str, Any]:
    """Idle seconds of the device (mean over device planes) by the label
    of the innermost host span, and by label and program: ``in <p>``
    between two ops of one execution of program ``p``, ``before <p>``
    where ``p`` is the next to start."""
    from benchmark import trace_reduce as tr

    host = events.get("host", [])
    segs = innermost_segments(host)
    seg_starts = [s[0] for s in segs]
    seg_edges = sorted({x for s in segs for x in s[:2]})
    edges = [e[1] for e in host] + [e[1] + e[2] for e in host]
    devices = events.get("devices", {})
    for dev in devices.values():
        for e in dev["ops"]:
            edges += [e[1], e[1] + e[2]]
    out: Dict[str, Any] = {"window_s": 0.0, "idle_s": 0.0, "by_phase": {},
                           "by_phase_and_program": {}}
    if not edges or not devices:
        return out
    t0, t1 = min(edges), max(edges)

    def label_at(t: float) -> str:
        k = bisect.bisect_right(seg_starts, t) - 1
        return label_of(segs[k][2]) if k >= 0 and t < segs[k][1] \
            else HARNESS    # no span open: the harness's loop

    by_phase: Dict[str, float] = {}
    by_both: Dict[str, float] = {}
    for dev in devices.values():
        mods = dev["modules"]
        mod_starts = [m[1] for m in mods]
        mod_edges = sorted({x for m in mods for x in (m[1], m[1] + m[2])})

        def program_at(t: float) -> str:
            i = bisect.bisect_right(mod_starts, t) - 1
            if i >= 0 and t < mods[i][1] + mods[i][2]:
                return "in " + tr._short(mods[i][0])
            return "before " + (tr._short(mods[i + 1][0])
                                if i + 1 < len(mods) else "end of trace")

        busy = tr._union([(e[1], e[1] + e[2]) for e in dev["ops"]
                          if not tr._is_container(e[0])])
        gaps = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(gaps[0::2], gaps[1::2]):
            if b <= a:
                continue
            # cut [a, b) where the innermost span or the program changes
            cuts = [a] + sorted(
                set(seg_edges[bisect.bisect_right(seg_edges, a):
                              bisect.bisect_left(seg_edges, b)])
                | set(mod_edges[bisect.bisect_right(mod_edges, a):
                                bisect.bisect_left(mod_edges, b)])) + [b]
            for lo, hi in zip(cuts, cuts[1:]):
                mid = (lo + hi) / 2.0
                label = label_at(mid)
                by_phase[label] = by_phase.get(label, 0.0) + (hi - lo)
                key = f"{label} | {program_at(mid)}"
                by_both[key] = by_both.get(key, 0.0) + (hi - lo)
    n = len(devices)
    out["window_s"] = (t1 - t0) / 1e9
    out["by_phase"] = {k: v / 1e9 / n for k, v in sorted(
        by_phase.items(), key=lambda kv: -kv[1])}
    out["by_phase_and_program"] = {k: v / 1e9 / n for k, v in sorted(
        by_both.items(), key=lambda kv: -kv[1])}
    out["idle_s"] = sum(out["by_phase"].values())
    return out


def span_walls(host: List[List[Any]]) -> Dict[str, List[float]]:
    """``{span: [count, seconds]}`` of the host spans in the trace."""
    walls: Dict[str, List[float]] = {}
    for name, _, dur in host:
        row = walls.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += dur / 1e9
    return walls


def nesting(host: List[List[Any]]) -> Dict[str, List[str]]:
    """``{span: the spans it was seen directly inside}``: shows that the
    engine's spans lie inside ``bench.step_iteration`` and each other as
    docs/observability.md says."""
    seen: Dict[str, set] = {}
    stack: List[Tuple[str, float]] = []
    for name, start, dur in sorted(host, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        seen.setdefault(name, set()).add(stack[-1][0] if stack else "")
        stack.append((name, start + dur))
    return {k: sorted(v) for k, v in seen.items()}


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark import trace_reduce as tr
    from benchmark.clock import CompileClock
    from benchmark.peaks import peaks_for

    t_process = time.time()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_chips(cell.chips)
    harness.place_compile_cache()
    clock = CompileClock()
    adapter, sched = harness.set_up(cell, args.seed, args.seconds)
    trace_dir = os.path.join(harness.CHECKOUT, ".bench_trace",
                             f"phases_{cell.name}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.time() - t_process
    window = harness.run_window(adapter, sched, args.seconds, trace_dir,
                                clock)
    kept = tr.HOST_PREFIX
    tr.HOST_PREFIX = PREFIXES   # str.startswith takes a tuple
    try:
        events = tr.load_xplane(tr.find_xplane(window.trace_path))
    finally:
        tr.HOST_PREFIX = kept
        shutil.rmtree(trace_dir, ignore_errors=True)
    red = tr.reduce_events(events)
    out = idle_by_phase(events)
    named = sum(v for k, v in out["by_phase"].items() if k != STEP_WRAP)
    a, b = window.stats_start, window.stats_end
    ctx = harness.Context(cell, window, setup_s, peaks_for(device["kind"]),
                          adapter, trace=red)
    out.update({
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "device": device, "busy_s": red.busy_s,
        "named_share": named / out["idle_s"] if out["idle_s"] else None,
        "span_walls": span_walls(events["host"]),
        "nesting": nesting(events["host"]),
        "stats_delta": {k: b[k] - a[k] for k in b if k in a},
        "per_layer": harness.read_metrics("layers", cell.per_layer, ctx),
        # a traced run's line has no end-to-end metric: here they are,
        # for the cost of the profiler's last four seconds
        "end_to_end": harness.read_metrics("end_to_end", cell.end_to_end,
                                           ctx),
        "compiles_in_window": window.compiles_in_window,
        "trace_stall_s": window.trace_stall_s,
        "slowest_steps": adapter.engine.slowest_steps()[:3]})
    print(f"{cell.name} seed {args.seed}: traced {out['window_s']:.3f} s, "
          f"busy {red.busy_s:.3f} s, idle {out['idle_s']:.4f} s")
    for label, secs in out["by_phase"].items():
        print(f"  {secs:9.5f} s  {secs / out['idle_s'] * 100:5.1f} %  "
              f"{label}")
    os.makedirs(os.path.join(harness.CHECKOUT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.CHECKOUT, "chiprun_out",
                        f"phases_{cell.name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
