"""Read the two ends a cell's ``gap_max`` limit is set from, on the chip,
at the cell's own size and load, many seeds in one process.

    python3 benchmark/tools/seeds.py --workload <cell> --seconds 15 \
        --seeds 11,12,13,... --control-seeds 3

For each seed: weights from the seed, the cell's traffic for a short
window through the timed path, the engine freed, then the plain reference
over the same sample a run compares. The *program's* reading is the widest
gap by which a served token's logit lies below the reference's best. For
the first ``--control-seeds`` seeds the *control's* reading is taken too:
the reference in the nearest lower precision, put in the program's place —
at each position of the same prompts and tokens, the gap (in the
reference) of the token the lower precision puts first. One JSON line a
seed goes to standard output and ``chiprun_out/seeds_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def read_seed(cell, seed: int, seconds: float, with_control: bool,
              clock=None) -> dict:
    from benchmark import harness

    t0 = time.time()
    ref_mod = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    adapter, sched = harness.set_up(cell, seed, seconds)
    t1 = time.time()
    window = harness.run_window(adapter, sched, seconds, None, clock)
    served = {r.index: adapter.tokens(r.handle) for r in window.requests
              if r.handle is not None}
    state = harness.resident_state(cell, window, adapter)
    for r in window.requests:
        r.handle = None
    adapter.free()
    del adapter
    t2 = time.time()
    compared = harness.decide_correct(cell, window, served, state, seed)
    sample = harness.check_sample(window, int(cell.params["check"]["requests"]),
                                  seed)
    by_index = {r.index: r for r in window.requests}
    row = {"workload": cell.name, "seed": seed, "seconds": seconds,
           "offered": len(window.requests),
           "failed": sum(1 for r in window.requests if not r.finished),
           "sampled_requests": len(sample),
           "sampled_tokens": sum(len(served[r.index]) for r in sample),
           "longest": max(int(r.prompt.size) + r.max_new for r in sample),
           "program_gap_max": compared["gap_max"]["value"],
           "limit": compared["gap_max"]["limit"],
           "kv_rows": [int(k.shape[1]) for k, _ in state.values()],
           "program_kv_gap_max": compared["kv_gap_max"]["value"],
           "kv_limit": compared["kv_gap_max"]["limit"],
           "correct": harness.is_correct(compared),
           "compared": compared,
           "setup_s": t1 - t0, "window_and_drain_s": t2 - t1}
    t3 = time.time()
    row["reference_s"] = t3 - t2
    if with_control:
        # the control in the program's place: the token it puts first at
        # each served position, and its own keys and values, through the
        # same comparison and the same limits as a run
        ctl = ref_mod.Reference(cell.config, seed, mode="control")
        firsts = {r.index: ctl.score(r.prompt, served[r.index])[1]
                  for r in harness.compared_requests(cell, window, state,
                                                     seed)}
        theirs = {i: ctl.score(by_index[i].prompt, served[i],
                               keep_kv=True)[2] for i in state}
        ctl.free()
        del ctl
        control = harness.decide_correct(cell, window, served, theirs,
                                         seed, claimed=firsts)
        row["control_gap_max"] = control["gap_max"]["value"]
        row["control_differs_at"] = sum(
            int(np.sum(np.asarray(firsts[i]) != np.asarray(served[i])))
            for i in firsts)
        row["control_kv_gap_max"] = control["kv_gap_max"]["value"]
        row["control_correct"] = harness.is_correct(control)
        row["control_s"] = time.time() - t3
    return row


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark.clock import CompileClock

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    clock = CompileClock()
    os.makedirs(os.path.join(harness.CHECKOUT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.CHECKOUT, "chiprun_out",
                        f"seeds_{cell.name}.jsonl")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = read_seed(cell, seed, args.seconds, i < args.control_seeds,
                        clock)
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
