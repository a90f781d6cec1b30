"""The one general traffic generator: an open loop read from a data file.

A traffic mix is a JSON file of parameters under ``benchmark/traffic/``;
this module turns it, a rate, a window length and ``--seed`` into a
schedule of requests. Every seed offers the same set of prompt lengths,
output lengths and inter-arrival gaps — the mid-quantiles of the mix's
distributions, so the offered work is identical — and draws the order of
each of the three, and the token ids, from the seed.

The order is stratified, not free: the sorted values are dealt round-robin
into blocks of ``block`` requests, so that every block holds a spread of
the whole distribution, and the seed shuffles inside each block and
shuffles the blocks. A free shuffle lets one seed put its long requests
together, and a statistic over some ninety requests then measures the
seed's luck and not the system (PERF.md, Findings).
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Request:
    index: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # (T,) int32 token ids
    max_new: int


def load_mix(name: str) -> Dict[str, Any]:
    path = os.path.join(_HERE, f"{name}.json")
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    if mix.get("generator") != "open_loop":
        raise ValueError(f"{path}: generator must be 'open_loop'")
    return mix


def _length_quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles of a clipped log-normal."""
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def _gap_quantiles(spec: Dict[str, Any], n: int, span_s: float
                   ) -> List[float]:
    """``n`` inter-arrival gaps that sum to ``span_s``: the mid-quantiles
    of an exponential, rescaled to the span. Not a Poisson process: the
    count in a window is fixed, only the order of the gaps is drawn."""
    kind = spec.get("process", "exponential_quantiles")
    if kind != "exponential_quantiles":
        raise ValueError(f"unknown arrival process {kind!r}")
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def balanced_order(values: List[Any], block: int,
                   rng: np.random.Generator) -> List[Any]:
    """Sorted ``values`` dealt round-robin into ceil(n/block) blocks, each
    block and the order of blocks shuffled by ``rng``."""
    n = len(values)
    n_blocks = max(1, -(-n // max(1, block)))
    srt = sorted(values)
    blocks: List[List[Any]] = [srt[b::n_blocks] for b in range(n_blocks)]
    order = rng.permutation(n_blocks)
    out: List[Any] = []
    for b in order:
        blk = blocks[int(b)]
        out.extend(blk[int(i)] for i in rng.permutation(len(blk)))
    return out


def schedule(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    """The requests due in a window of ``seconds`` at ``rate_rps``."""
    n = int(round(rate_rps * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_rps}/s over {seconds}s offers no "
                         "request")
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    block = int(mix.get("block", 8))
    prompts = balanced_order(_length_quantiles(mix["prompt_len"], n),
                             block, rng)
    outputs = balanced_order(_length_quantiles(mix["output_len"], n),
                             block, rng)
    # the last arrival falls half a mean gap before the window closes
    gaps = balanced_order(
        _gap_quantiles(mix.get("arrivals", {}), n,
                       seconds - 0.5 / rate_rps), block, rng)
    due = np.cumsum(gaps)
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab, size=prompts[i], dtype=np.int32)
        reqs.append(Request(i, float(due[i]), ids, int(outputs[i])))
    return reqs
