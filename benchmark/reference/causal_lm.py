"""Plain reference of the GPT-2-style causal LM the configurations state.

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision: no KV cache, no batching, no buckets, no kernels. It imports
nothing of the program and takes nothing the program made: the weights are
drawn here from the seed by the recipe the configuration file states
(``weights``), and for ``quantize`` configurations they are quantized here
by the scheme the file states (``quantization``).

Departures from the published architecture (the program has the same, see
the configuration's ``departures``): no biases, LayerNorm with a scale and
no bias at eps 1e-6, tanh-approximated GELU.

``mode`` selects the arithmetic:

* ``"reference"`` — what the configuration states.
* ``"control"`` — the nearest precision below it, the step that would
  tempt a later PR: ``high`` for a configuration that states float32 at
  ``highest`` (three bfloat16 passes a1·b1 + a1·b2 + a2·b1 with float32
  accumulation, written out so that it is the same arithmetic on any
  backend), int4 weights for a w8a8 one. ``correct`` has to come out false
  for it (benchmark/tools/seeds.py reads it on the chip,
  benchmark/tests/test_correct.py at a small size).

The forward pass runs layer by layer, one sequence at a time, padded to a
power of two (rows past the sequence are hidden by the causal mask) so
that a handful of small programs serve every length.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def init_params(seed: int, cfg: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The configuration's ``weights`` recipe: six keys split from
    ``PRNGKey(seed)``; embeddings N(0, 0.02²); GEMM stacks N(0, 1/fan_in);
    norm scales one."""
    v, d, n = cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"]
    f, m = cfg["n_inner"], cfg["n_positions"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)
        s, sf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
        return {
            "embed": jax.random.normal(ks[0], (v, d)) * 0.02,
            "pos_embed": jax.random.normal(ks[1], (m, d)) * 0.02,
            "wqkv": jax.random.normal(ks[2], (n, d, 3 * d)) * s,
            "wo": jax.random.normal(ks[3], (n, d, d)) * s,
            "w1": jax.random.normal(ks[4], (n, d, f)) * s,
            "w2": jax.random.normal(ks[5], (n, f, d)) * sf,
            "ln1": jnp.ones((n, d)), "ln2": jnp.ones((n, d)),
            "lnf": jnp.ones((d,)),
        }

    return make(jax.random.PRNGKey(int(seed)))


@partial(jax.jit, static_argnames=("levels",))
def _quantize_weight(w, levels):
    """Per-output-channel absmax grid over the contracted axis."""
    absmax = jnp.max(jnp.abs(w), axis=-2)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / levels)
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -levels, levels)
    return q.astype(jnp.int8), scale


def _quant_act(x):
    """Per-row (per-token) dynamic absmax int8 grid."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _split(x):
    """A float32 array as two bfloat16 pieces, x = hi + lo to 16 bits.
    ``reduce_precision`` and not a cast there and back, which XLA is free
    to drop (``xla_allow_excess_precision``): on the chip that left one
    bfloat16 pass, and the control read 500 times too far off."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _contract(spec, a, b, high):
    """Float contraction at ``highest``, or the control's ``high``."""
    if not high:
        return jnp.einsum(spec, a, b, precision=_HI)
    (a1, a2), (b1, b2) = _split(a), _split(b)
    one = partial(jnp.einsum, spec, preferred_element_type=jnp.float32)
    return one(a1, b1) + one(a1, b2) + one(a2, b1)


def _qmm(x, w):
    """w8a8 matmul: int8 codes both sides, exact int32 accumulation."""
    xq, xs = _quant_act(x)
    y = jax.lax.dot_general(xq, w["q"], (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * xs * w["s"]


@partial(jax.jit, static_argnames=("n_heads", "quant", "high"))
def _layer(h, layer, n_heads, quant, high):
    """One block over a whole sequence (T, D) with a causal mask. Returns
    the block's output and its keys and values, (H, T, head) each."""
    t, d = h.shape
    hd = d // n_heads
    mm = _qmm if quant else (lambda x, w: _contract("td,df->tf", x, w, high))
    a = _ln(h, layer["ln1"])
    q, k, v = jnp.split(mm(a, layer["wqkv"]), 3, axis=-1)
    q, k, v = (z.reshape(t, n_heads, hd).transpose(1, 0, 2)
               for z in (q, k, v))
    s = _contract("hqd,hkd->hqk", q, k, high) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    o = _contract("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, high)
    h = h + mm(o.transpose(1, 0, 2).reshape(t, d), layer["wo"])
    m = _ln(h, layer["ln2"])
    return h + mm(_gelu(mm(m, layer["w1"])), layer["w2"]), k, v


@partial(jax.jit, static_argnames=("high",))
def _score(h, lnf, embed, query, high):
    """For each row: how far the queried token's logit lies below the
    best, and the best token."""
    logits = _contract("td,vd->tv", _ln(h, lnf), embed, high)
    got = jnp.take_along_axis(logits, query[:, None], axis=-1)[:, 0]
    return logits.max(-1) - got, jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def _rel_gap(got, want, rows):
    """Over the first ``rows`` rows of (H, T, head) arrays: the norm of
    the difference over the norm of what was wanted."""
    live = (jnp.arange(want.shape[1]) < rows)[None, :, None]
    return jnp.linalg.norm(jnp.where(live, got - want, 0.0).ravel()) \
        / jnp.linalg.norm(jnp.where(live, want, 0.0).ravel())


class Reference:
    """Weights from the seed, then ``score`` over served sequences."""

    def __init__(self, cfg: Dict[str, Any], seed: int,
                 mode: str = "reference") -> None:
        if mode not in ("reference", "control"):
            raise ValueError(f"unknown mode {mode!r}")
        self.cfg = cfg
        self.quant = bool(cfg.get("quantize"))
        self.high = mode == "control" and not self.quant
        levels = 7 if mode == "control" else 127  # int4 grid, or int8
        p = init_params(seed, cfg)
        if self.quant:
            for k in ("wqkv", "wo", "w1", "w2"):
                q, s = _quantize_weight(p[k], levels)
                p[k] = {"q": q, "s": s}
        self.params = p

    def _layer_params(self, i: int) -> Dict[str, Any]:
        return jax.tree_util.tree_map(
            lambda x: x[i],
            {k: self.params[k]
             for k in ("wqkv", "wo", "w1", "w2", "ln1", "ln2")})

    def score(self, prompt: np.ndarray, served: Sequence[int],
              query: Optional[Sequence[int]] = None,
              kv: Optional[Tuple[np.ndarray, np.ndarray]] = None,
              keep_kv: bool = False):
        """One forward over the prompt and what was served after it
        (teacher-forced). Returns, for each served position, how far the
        logit of ``query``'s token (default: the served token) lies below
        this model's best there, and this model's best token; then, given
        ``kv`` — the keys and values some other arithmetic holds for the
        same sequence, (layers*heads, rows, head) each — the widest
        relative gap of a layer's keys or values from this model's; then,
        with ``keep_kv``, this model's own keys and values in that layout."""
        served = np.asarray(served, np.int32)
        query = served if query is None else np.asarray(query, np.int32)
        n, t = int(served.size), int(prompt.size)
        seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
        rows, heads = int(seq.size), int(self.cfg["n_head"])
        tp = 128
        while tp < rows:
            tp *= 2
        ids = np.zeros((tp,), np.int32)
        ids[:rows] = seq
        want = np.zeros((tp,), np.int32)
        want[t - 1:t - 1 + n] = query
        h = self.params["embed"][jnp.asarray(ids)] \
            + self.params["pos_embed"][:tp]
        kv_gap, kept = 0.0, ([], [])
        for i in range(self.cfg["n_layer"]):
            h, k, v = _layer(h, self._layer_params(i), heads, self.quant,
                             self.high)
            for j, mine in enumerate((k, v)):
                if kv is not None:
                    theirs = np.zeros(mine.shape, np.float32)
                    theirs[:, :rows] = kv[j][i * heads:(i + 1) * heads,
                                             :rows]
                    kv_gap = max(kv_gap, float(_rel_gap(
                        jnp.asarray(theirs), mine, rows)))
                if keep_kv:
                    kept[j].append(np.asarray(mine[:, :rows]))
        gap, best = _score(h, self.params["lnf"], self.params["embed"],
                           jnp.asarray(want), self.high)
        at = slice(t - 1, t - 1 + n)
        out = (np.asarray(gap)[at], np.asarray(best)[at])
        if kv is not None:
            out += (kv_gap,)
        if keep_kv:
            out += (tuple(np.concatenate(x) for x in kept),)
        return out

    def free(self) -> None:
        self.params = None
