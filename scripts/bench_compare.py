#!/usr/bin/env python
"""Compare a fresh bench.py run against the newest committed BENCH_r*.json.

    python bench.py > /tmp/fresh.json
    python scripts/bench_compare.py /tmp/fresh.json

Flags a regression when a named lane moves more than ``--threshold``
(default 10%) in its bad direction — throughput/utilization lanes down,
latency/waste lanes up — and exits nonzero so a CI step can gate on it.

Input formats (both sides accept either):
  * a plain bench.py result dict, or
  * a committed driver artifact ``{n, cmd, rc, tail, parsed}`` — the
    result is ``parsed`` when the driver captured it, else lane values
    are recovered from the ``tail`` text (the tail may truncate the
    JSON's head, so this regexes ``"lane": number`` pairs rather than
    parsing).

Renamed lanes are followed through ``ALIASES`` (e.g. the honest
``adaptive_batch16_pipeline_util`` reads old baselines' mislabelled
``adaptive_batch16_mfu``), so a rename never fakes a vanished lane.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, Optional, Tuple

#: named lanes -> direction: +1 higher-is-better, -1 lower-is-better.
#: Curated, not exhaustive: these are the headline lanes CHANGES/ROADMAP
#: quote; one-off diagnostic fields move too much run-to-run to gate on.
LANES: Dict[str, int] = {
    # headline lanes (present since the earliest artifacts)
    "fps_median": +1,
    "mfu": +1,
    "vs_baseline": +1,
    "p50_invoke_us": -1,
    "composite_lstm_query_fps_median": +1,
    "adaptive_batch16_fps_median": +1,
    "adaptive_batch16_pipeline_util": +1,
    "transformer_prefill_b64_tokens_per_s": +1,
    "transformer_roofline_tokens_per_s": +1,
    "transformer_roofline_mfu": +1,
    "transformer_roofline_w8a8_tokens_per_s": +1,
    "transformer_roofline_w8a8_int8_util": +1,
    "lm_serving_continuous_tokens_per_s": +1,
    "lm_serving_speedup": +1,
    "lm_serving_spec_tokens_per_s": +1,
    "composite_roundtrip_p50_us": -1,
    "transformer_roofline_step_s_median": -1,
    "lm_serving_continuous_waste_frac": -1,
    "multiplex_fps_median": +1,
    "multiplex_pipeline_util": +1,
    # per-tenant goodput under the 8-tenant mix (obs.slo accounting):
    # deadline-met work as a fraction of all work, overall and for the
    # deadline-tight tenant — a scheduler "win" that starves the tight
    # tenant regresses here even when occupancy improves
    "multiplex_goodput_ratio": +1,
    "multiplex_goodput_tight_ratio": +1,
    # disaggregated prefill/decode serving (serving/disagg.py): the
    # absolute rate, the cost of the wire hop against the same engine
    # unified, and the prefix reuse the radix digest router exists for
    "disagg_serving_tokens_per_s": +1,
    "disagg_serving_relative": +1,
    "disagg_serving_prefix_hit_rate": +1,
    "lm_serving_paged_prefix_hit_rate": +1,
    # epilogue fusion (ops/epilogue.py): post-filter chains compiled into
    # the filter's jit — fewer dispatches per frame is the tentpole claim
    "epilogue_fusion_fps_median": +1,
    "epilogue_fusion_speedup": +1,
    "epilogue_fusion_dispatches_per_frame": -1,
    "epilogue_fusion_dispatch_ratio": +1,
    # autotuner (tune/): a warm store must answer without sweeping
    # (0 is the contract, any growth is a persistence regression), and
    # the tuner's flash-block pick must match or beat the round-5
    # hand sweep it replaces (ratio >= 1)
    "autotune_warm_sweeps": -1,
    "autotune_flash_vs_hand": +1,
    "autotune_flash_tuned_ms": -1,
    # fleet autoscaling (fleet/): live session migration must stay
    # cheap (wall seconds per migrated session, end to end including
    # the KV-page ship), and goodput after halving the fleet under
    # load must hold against the unhalved run (ratio >= the SLO floor
    # — streams surviving a scale-in is the tentpole claim)
    "fleet_migration_seconds": -1,
    "fleet_halved_goodput_ratio": +1,
    # crash restore (fleet/checkpoint.py): restoring a killed worker's
    # sessions must stay fast (re-pin + checkpoint_send + page splice,
    # end to end) and warm (post-restore prompt tokens served from the
    # restored prefix pages — a re-prefill fallback scores ~0 here)
    "fleet_restore_seconds": -1,
    "fleet_restore_warm_ratio": +1,
    "fleet_checkpoint_overhead_ratio": +1,
    # incident diagnostics (obs/diag/): freezing a full debug bundle
    # must stay cheap enough to fire from a burn alert in production,
    # and the critical-path sweep must keep attributing root-span time
    # to real segments (a coverage drop means the taps stopped seeing
    # the latency they are supposed to explain)
    "diag_capture_seconds": -1,
    "diag_critpath_coverage_ratio": +1,
    # data-plane quality (obs/quality/): the instrumented pipeline must
    # keep >= 95% of the uninstrumented rate (the <= 5% overhead
    # acceptance gate rides this ratio), and a frozen-baseline
    # distribution shift must breach both drift windows quickly
    "quality_overhead_ratio": +1,
    "quality_drift_detect_seconds": -1,
}

#: absolute floors, gated on the FRESH run independently of the
#: baseline — a drifting baseline must never grandfather a breach.
#: fleet_checkpoint_overhead_ratio is the checkpoint daemon's
#: acceptance gate: serving throughput with a checkpoint pass per
#: request holds >= 95% of the uncheckpointed rate.
FLOORS: Dict[str, float] = {
    "fleet_checkpoint_overhead_ratio": 0.95,
}

#: current lane name -> names it may carry in OLDER baselines
ALIASES: Dict[str, Tuple[str, ...]] = {
    "adaptive_batch16_pipeline_util": ("adaptive_batch16_mfu",),
    # the multi-tenant scheduler lane supersedes the serial utilization
    # number: older baselines carry only the 1-pipeline figure, and the
    # whole point of sched.DeviceEngine is the delta against it
    "multiplex_pipeline_util": ("adaptive_batch16_pipeline_util",
                                "adaptive_batch16_mfu"),
}

_NUM_RE = re.compile(r'"([A-Za-z0-9_]+)":\s*(-?\d+(?:\.\d+)?(?:[eE][-+]?\d+)?)')


def _lanes_from_tail(tail: str) -> Dict[str, float]:
    """Recover scalar lanes from a (possibly head-truncated) result
    tail. Last occurrence wins — matches dict-update semantics."""
    return {k: float(v) for k, v in _NUM_RE.findall(tail or "")}


def load_lanes(path: str) -> Dict[str, float]:
    """Scalar lane values from a bench result file (plain or wrapped)."""
    with open(path, "r", encoding="utf-8") as fp:
        doc = json.load(fp)
    if isinstance(doc, dict) and "tail" in doc and "rc" in doc:  # wrapped
        parsed = doc.get("parsed")
        doc = parsed if isinstance(parsed, dict) \
            else _lanes_from_tail(doc.get("tail", ""))
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench result dict")
    return {k: float(v) for k, v in doc.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def newest_baseline(root: str) -> Optional[str]:
    """Newest committed BENCH_r*.json by round number (name sort is the
    commit order: BENCH_r01 < BENCH_r02 < ...)."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    return paths[-1] if paths else None


def lane_value(lanes: Dict[str, float], name: str) -> Optional[float]:
    if name in lanes:
        return lanes[name]
    for alias in ALIASES.get(name, ()):
        if alias in lanes:
            return lanes[alias]
    return None


def compare(fresh: Dict[str, float], base: Dict[str, float],
            threshold: float, lane_names) -> Tuple[list, list, list]:
    """-> (regressions, ok, skipped) rows of (lane, base, fresh, delta)."""
    regressions, ok, skipped = [], [], []
    for name in lane_names:
        sign = LANES.get(name, +1)
        # aliases resolve the BASELINE side only: a fresh artifact may
        # legitimately carry both a lane and the older lane it
        # supersedes (multiplex_pipeline_util next to
        # adaptive_batch16_pipeline_util) — the old value must never
        # stand in for a missing new reading
        b, f = lane_value(base, name), fresh.get(name)
        if b is None or f is None or b == 0:
            skipped.append((name, b, f, None))
            continue
        delta = (f - b) / abs(b)
        row = (name, b, f, delta)
        # bad direction: down for higher-is-better, up for lower-is-better
        if sign * delta < -threshold if sign > 0 else delta > threshold:
            regressions.append(row)
        else:
            ok.append(row)
    return regressions, ok, skipped


def main(argv=None) -> int:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(
        description="flag >threshold regressions vs the newest committed "
                    "BENCH_r*.json")
    ap.add_argument("fresh", help="fresh bench result JSON (plain bench.py "
                                  "stdout or a wrapped driver artifact)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: newest BENCH_r*.json in "
                         "the repo root)")
    ap.add_argument("--threshold", type=float, default=0.10, metavar="FRAC",
                    help="regression threshold as a fraction (default 0.10)")
    ap.add_argument("--lanes", default=None,
                    help="comma-separated lane names (default: the curated "
                         "named-lane set)")
    args = ap.parse_args(argv)

    baseline = args.baseline or newest_baseline(repo_root)
    if baseline is None:
        print("bench_compare: no BENCH_r*.json baseline found", file=sys.stderr)
        return 2
    try:
        fresh = load_lanes(args.fresh)
        base = load_lanes(baseline)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    lane_names = [ln.strip() for ln in args.lanes.split(",") if ln.strip()] \
        if args.lanes else list(LANES)
    regressions, ok, skipped = compare(fresh, base, args.threshold, lane_names)
    floor_breaches = [(name, FLOORS[name], fresh[name])
                      for name in sorted(FLOORS)
                      if name in fresh and fresh[name] < FLOORS[name]]

    print(f"baseline: {baseline}")
    for name, b, f, d in ok:
        arrow = "+" if d >= 0 else ""
        print(f"  ok        {name}: {b:g} -> {f:g} ({arrow}{d * 100:.1f}%)")
    for name, b, f, _ in skipped:
        which = "both" if b is None and f is None else \
            ("baseline" if b is None else "fresh")
        print(f"  skipped   {name}: missing in {which}")
    for name, b, f, d in regressions:
        print(f"  REGRESSED {name}: {b:g} -> {f:g} ({d * 100:+.1f}%, "
              f"threshold {args.threshold * 100:.0f}%)")
    for name, fl, f in floor_breaches:
        print(f"  FLOOR     {name}: {f:g} below absolute floor {fl:g}")
    if regressions or floor_breaches:
        print(f"bench_compare: {len(regressions)} lane(s) regressed, "
              f"{len(floor_breaches)} floor breach(es)", file=sys.stderr)
        return 1
    print(f"bench_compare: {len(ok)} lane(s) within threshold, "
          f"{len(skipped)} skipped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
