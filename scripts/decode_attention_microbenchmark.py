"""The decode attention kernel alone, form against form: what a decode
step's 24 calls of ``pallas.decode_attention`` cost on the attached chip.

    chiprun -- python scripts/decode_attention_microbenchmark.py \
        PARENT_CHECKOUT .:32:4 .:64:4 .:256:4 .:32:1 .:32:16

and at Qwen3-Next's grouped shape (2 calls a step, 16 query heads over 2
K/V heads of 256):

    python scripts/decode_attention_microbenchmark.py \
        PARENT_CHECKOUT . --dims 16,2,2,2048,256 --slots 64 --hi 700

A form is ``ROOT[:GRAIN[:HEADS]]``: the kernel of the checkout at ROOT
(``.`` is this one), with ``_TAIL_ROWS`` (a tail's sub-tile) set to GRAIN
and ``_HEAD_UNROLL`` (heads a trip of an item's loop) to HEADS where given;
an older checkout that has neither runs as it is. ``decode_attention.
_TAIL_ROWS`` and ``_HEAD_UNROLL`` were chosen from this table (PERF.md, PR
36). Every set puts ``--slots`` streams at positions drawn from ``--seed``
between ``--lo`` and ``--hi`` (by default the overload cell's 2,770-2,930
live rows over 8 slots, the tails spread over the block); every form runs
every set, in turn. Prints one JSON line a form: ms a step (the median over
sets of each set's median of ``--reps`` steps), the seconds to lower and to
compile the step (a lowering is paid again in every process, cache hit or
not: ``setup_s``), store rows read and live a step, and the output's
largest gap from the first form's. ``--dims`` gives the query heads, the
K/V heads (fewer than the query heads where the queries are grouped), the
layers, ``max_len`` and the head size; by default Cerebras-GPT-1.3B's.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_PKG = "nnstreamer_tpu"


def load_kernel(root: str):
    """A fresh copy of ``root``'s kernel module (its own jit caches, so a
    setting changed on it is traced anew), the running package untouched."""
    def ours():
        return {k: m for k, m in sys.modules.items()
                if k == _PKG or k.startswith(_PKG + ".")}

    saved = ours()
    for k in saved:
        del sys.modules[k]
    sys.path.insert(0, os.path.abspath(root))
    try:
        return importlib.import_module(_PKG + ".ops.pallas.decode_attention")
    finally:
        sys.path.pop(0)
        for k in ours():
            del sys.modules[k]
        sys.modules.update(saved)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("forms", nargs="+", help="ROOT[:GRAIN[:HEADS]]")
    ap.add_argument("--dims", default="16,16,24,2048,128",
                    help="query heads,K/V heads,layers,max_len,head size")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--lo", type=int, default=32)
    ap.add_argument("--hi", type=int, default=680)
    ap.add_argument("--sets", type=int, default=16)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=36)
    args = ap.parse_args()
    hq, h, n_layers, max_len, hd = map(int, args.dims.split(","))
    s = args.slots
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "dims": args.dims, "slots": s}), flush=True)
    q = jax.random.normal(jax.random.PRNGKey(args.seed), (s, hq, 1, hd),
                          jnp.float32)
    row = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                            (2, s, h, 1, hd), jnp.float32)
    store = (s, n_layers, h, max_len, hd)
    kc = jax.random.normal(jax.random.PRNGKey(1), store, jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(2), store, jnp.float32)
    active = jnp.ones((s,), bool)

    def step(da, kc, vc, pos):
        o = []
        for li in range(n_layers):
            oi, kc, vc = da.decode_attention(q, row[0], row[1], kc, vc, li,
                                             pos, active, layer_axis=1)
            o.append(oi)
        return jnp.stack(o), kc, vc

    forms = {}
    for spec in args.forms:
        root, *knobs = spec.split(":")
        da = load_kernel(root)
        for name, value in zip(("_TAIL_ROWS", "_HEAD_UNROLL"), knobs):
            setattr(da, name, int(value))
        grain = da.read_grain(max_len) if hasattr(da, "read_grain") \
            else da.kv_block(max_len)
        forms[spec] = dict(fn=jax.jit(functools.partial(step, da),
                                      donate_argnums=(0, 1)),
                           grain=grain, ms=[], gap=0.0)
    rng = np.random.default_rng(args.seed)
    sets = [rng.integers(args.lo, args.hi + 1, s) for _ in range(args.sets)]
    for pos in sets:
        p = jnp.asarray(pos, jnp.int32)
        first = None
        for f in forms.values():
            if "run" not in f:
                t0 = time.perf_counter()
                lowered = f["fn"].lower(kc, vc, p)
                f["lower_s"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                f["run"] = lowered.compile()
                f["compile_s"] = time.perf_counter() - t0
            o, kc, vc = f["run"](kc, vc, p)
            o = np.asarray(o)
            first = o if first is None else first
            f["gap"] = max(f["gap"], float(np.abs(o - first).max()))
            reps = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                o, kc, vc = f["run"](kc, vc, p)
                o.block_until_ready()
                reps.append(time.perf_counter() - t0)
            f["ms"].append(statistics.median(reps) * 1e3)
    live = statistics.mean(int(sum(pos)) for pos in sets)
    out = []
    for spec, f in forms.items():
        g = f["grain"]
        line = {"form": spec, "grain": g,
                "ms_a_step": statistics.median(f["ms"]), "ms_sets": f["ms"],
                "lower_s": f["lower_s"], "compile_s": f["compile_s"],
                "rows_read": statistics.mean(
                    sum(-(-int(x) // g) * g for x in pos) for pos in sets),
                "rows_live": live, "gap_to_first": f["gap"]}
        out.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "decode_attention_forms.jsonl"),
              "w") as fh:
        fh.writelines(json.dumps(line) + "\n" for line in out)


if __name__ == "__main__":
    main()
