"""Find a cell's knee once, on the chip: one process, one engine, a window
at each of a few fixed rates.

    python3 benchmark/tools/sweep.py --workload <cell> --seconds 25 \
        --rates 1.5,2,2.5,3 [--seed 1]

The knee is the highest rate the engine sustains: completed requests keep
up with offered ones and the backlog at the window's close is what a
steady queue holds, not what a growing one has piled up. One JSON line a
rate goes to standard output and to ``chiprun_out/sweep_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark.clock import CompileClock
    from benchmark.end_to_end import (out_tokens_per_s, tpot_p90_ms,
                                      ttft_p90_ms)
    from benchmark.layers import ttft_mean_ms
    from benchmark.stats import percentile
    from benchmark.traffic import open_loop

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="",
                    help="one seed a rate (default: seed, seed+1, ..)")
    args = ap.parse_args(argv)
    t0 = time.time()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    clock = CompileClock()
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")] if args.seeds \
        else [args.seed + i for i in range(len(rates))]
    builder = importlib.import_module(
        f"benchmark.builders.{cell.config['builder']}")
    adapter = builder.build(cell.config, args.seed)
    vocab = int(cell.config["vocab_size"])
    probe = open_loop.schedule(cell.mix, max(rates), args.seconds,
                               args.seed, vocab)
    adapter.warm([r.prompt.size for r in probe], vocab)
    print(f"setup {time.time() - t0:.1f}s {clock.snapshot()}", flush=True)
    os.makedirs(os.path.join(harness.CHECKOUT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.CHECKOUT, "chiprun_out",
                        f"sweep_{cell.name}.jsonl")
    for i, rate in enumerate(rates):
        sched = open_loop.schedule(cell.mix, rate, args.seconds,
                                   seeds[i], vocab)
        w = harness.run_window(adapter, sched, args.seconds, None, clock)
        ctx = harness.Context(cell, w, 0.0, {}, adapter)
        in_win = [it for it in w.iterations if it.in_window]
        done = sum(1 for r in w.requests
                   if r.finished and r.token_s[-1] <= w.seconds)
        row = {
            "workload": cell.name, "rate_rps": rate, "seed": seeds[i],
            "seconds": args.seconds, "offered": len(w.requests),
            "completed_in_window": done,
            "ttft_p50_ms": percentile(
                [(r.token_s[0] - r.due_s) * 1e3 for r in w.requests
                 if r.token_s], 50.0),
            "ttft_mean_ms": ttft_mean_ms.read(ctx),
            "ttft_p90_ms": ttft_p90_ms.read(ctx),
            "tpot_p90_ms": tpot_p90_ms.read(ctx),
            "out_tokens_per_s": out_tokens_per_s.read(ctx),
            "pending_max": max((it.pending_after for it in in_win),
                               default=0),
            "pending_at_close": in_win[-1].pending_after if in_win else 0,
            "iterations": len(in_win),
            "iter_ms_p50": percentile(
                [(it.t1 - it.t0) * 1e3 for it in in_win], 50.0),
            "drain_s": w.drain_s,
            "compiles_in_window": w.compiles_in_window,
            "memory_peak_bytes": harness.memory_peak_bytes(cell.chips),
        }
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
