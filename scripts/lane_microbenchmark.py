"""The mixed step's microbenchmark: what a decode step of the contiguous
``LMEngine`` costs with P prompt rows under its decode rows, against a
step with none, at a model's production shape on the attached chip.

    chiprun -- python scripts/lane_microbenchmark.py [--rows 0,32,64,128]

``serving/lm_engine.LANE_ROWS`` was chosen from this table (PERF.md, PR
29). The program takes its lane's width from the plan's shape, so every
width runs the engine's own ``_decode_chunk``: 8 slots of which ``--active``
decode at ``--pos`` rows, a chunk of ``--steps`` steps, the lane's windows
following each other from ``--lane-pos`` in a slot that does not decode.
Prints one JSON line a width: compile seconds, ms a step (the median of
``--reps`` chunks), and the step's cost over the step without a lane.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nnstreamer_tpu.models import causal_lm  # noqa: E402
from nnstreamer_tpu.serving import lm_engine  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="0,32,64,128")
    ap.add_argument("--dims", default="50257,2048,16,24,2048,8192",
                    help="vocab,d_model,heads,layers,max_len,d_ff")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--active", type=int, default=2)
    ap.add_argument("--pos", type=int, default=400)
    ap.add_argument("--lane-pos", type=int, default=0)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--reps", type=int, default=8)
    args = ap.parse_args()
    v, d, h, n_layers, max_len, d_ff = map(int, args.dims.split(","))
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    params = jax.jit(lambda key: causal_lm.init_causal_lm(
        key, v, d, h, n_layers, max_len, d_ff))(jax.random.PRNGKey(0))
    s = args.slots
    shape = (s, n_layers * h, max_len, d // h)
    # what the rows hold does not move a time
    kc, vc = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    active = np.arange(s) < args.active
    target = s - 1
    base = None
    for p in map(int, args.rows.split(",")):
        tokens = jnp.zeros((s, 1, 1), jnp.int32)
        pos = jnp.full((s, 1), args.pos, jnp.int32)
        lane = None
        if p:
            lane = np.zeros((args.steps, 4 + p), np.int32)
            lane[:, 0] = target
            lane[:, 1] = args.lane_pos + p * np.arange(args.steps)
            lane[:, 2] = p
            lane[:, 4:] = np.arange(p)[None] + 1

        def run(tokens, kc, vc, pos):
            return lm_engine._decode_chunk(
                params, tokens, kc, vc, pos, active,
                np.zeros((s, 2), np.uint32), np.zeros((s,), np.float32),
                np.zeros((s,), np.int32), np.ones((s,), np.float32),
                None if lane is None else (lane, np.int32(args.steps)),
                n_heads=h, n_steps=args.steps)

        t0 = time.perf_counter()
        tokens, kc, vc, _, outs, _ = run(tokens, kc, vc, pos)
        np.asarray(outs)
        first = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            pos = jnp.full((s, 1), args.pos, jnp.int32)
            jax.block_until_ready(pos)
            t0 = time.perf_counter()
            tokens, kc, vc, _, outs, _ = run(tokens, kc, vc, pos)
            np.asarray(outs)
            times.append((time.perf_counter() - t0) * 1e3 / args.steps)
        ms = statistics.median(times)
        if not p:
            base = ms
        print(json.dumps({
            "lane_rows": p, "first_call_s": first, "ms_per_step": ms,
            "ms_min": min(times), "ms_max": max(times),
            "over_plain_ms": None if base is None else ms - base,
            "ms_per_ktok_extra": None if base is None or not p
            else (ms - base) * 1e3 / p}), flush=True)


if __name__ == "__main__":
    main()
