"""The useful work of a set of iterations, from the harness's own records:
which prompts were prefilled and which decode steps produced a token that
a request kept. Padded bucket tokens, empty slots and tokens past a
request's end are not work."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterable, List

from . import flops


@dataclass
class Work:
    prefills: List[int]            # true prompt lengths
    buckets: List[int]             # the padded lengths they ran at
    decode_steps: int              # device steps (all slots each)
    kept_slot_steps: int           # slot-steps whose token a request kept
    prefill_flops: float
    decode_flops: float
    decode_kv_rows: int            # live K/V rows the kept steps attended


def tally(ctx: Any, iterations: Iterable[Any]) -> Work:
    m = flops.model_dims(ctx.cell.config)
    dims = (m["d_model"], m["n_layers"], m["vocab"], m["d_ff"])
    by_index = {r.index: r for r in ctx.window.requests}
    w = Work([], [], 0, 0, 0.0, 0.0, 0)
    for it in iterations:
        w.decode_steps += it.decode_steps
        for index, before, after in it.progress:
            t = int(by_index[index].prompt.size)
            if before == 0:
                # admitted here: the prefill gave the first token
                w.prefills.append(t)
                w.buckets.append(ctx.adapter.bucket_of(t))
                w.prefill_flops += flops.prefill_flops(1, t, *dims)
                pos0, kept = t, after - 1
            else:
                pos0, kept = t + before - 1, after - before
            if kept > 0:
                w.kept_slot_steps += kept
                w.decode_flops += flops.decode_flops(1, pos0, kept, *dims)
                w.decode_kv_rows += kept * (pos0 + 1) \
                    + kept * (kept - 1) // 2
    return w


def traced_iterations(ctx: Any) -> List[Any]:
    return [it for it in ctx.window.iterations if it.traced]
