"""Plain reference of GLM-4.7-Flash's block (``glm4_moe_lite``) as the
configuration states it.

Straightforward ``jax.numpy`` in float32 with every contraction at
``highest``: no cache, no batching, no kernels, the naive (unabsorbed)
latent attention, a loop over all experts. It imports nothing of the
program and takes nothing the program made: the weights are drawn here from
the seed by the recipe the configuration file states (``weights``).

Layer equations (x a row of ``hidden_size``; RMSNorm with a scale, eps
``rms_norm_eps``, before attention, before the MLP and before the head):

* latent attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a head's
  ``qk_nope_head_dim`` dims without position and ``qk_rope_head_dim`` with;
  ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``; rotary (``rope_theta``,
  the two halves of the rotary dims are the pairs: ``assumed``) on each
  head's ``q_r`` and on the one shared ``k_r``; ``[k_n | v] = c_kv W_kvb`` a
  head; scores ``(q_n . k_n + q_r . k_r) / sqrt(nope + rope)``, causal
  softmax, ``o = sum p v``, ``out = concat(o) W_o``. No bias anywhere.
* the first ``first_k_dense_replace`` layers: ``W_down(silu(x W_gate) * x
  W_up)`` at ``intermediate_size``.
* the others: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of
  ``s + b`` are picked (``b`` the correction bias; one group, so no group
  limit); their weights are the picked ``s`` WITHOUT ``b`` over their sum,
  times ``routed_scaling_factor``; ``y = sum w_e E_e(x) + E_shared(x)``,
  every E a gated SiLU MLP at ``moe_intermediate_size``. Every row gets
  every expert it picked.

``mode`` selects the arithmetic: ``"reference"`` is what the configuration
states; ``"control"`` is the nearest precision below it, ``high`` (three
bfloat16 passes a1.b1 + a1.b2 + a2.b1 with float32 accumulation, written
out so that it is the same arithmetic on any backend). ``correct`` has to
come out false for the control.

**Routing ties (``route_tie``).** Program and reference each round a
``hidden_size``-term sum into a router score. Where a row's last picked
and first unpicked selection scores lie closer than that rounding, the two
pick different experts and everything after parts far beyond any float
tolerance, with neither wrong. So at a token-layer whose two scores lie
within ``route_tie`` (the configuration states it, at least ten times the
score error measured, with its reason) both picks are the model: the
reference evaluates both, in the order of layers and rows, and goes on
with the one under which what it compares lies closer: where the store's
rows are given, that token's own rows in the next layer (they depend on
this pick and on earlier ones alone); else the served tokens' gaps,
summed. ``free()`` prints how many token-layers were tied and
how many went with the second pick, with the compared numbers.

The forward pass runs layer by layer, one sequence at a time, padded to a
power of two (rows past the sequence are hidden by the causal mask). The
routed experts' weights (three leaves of 0.8 GB a layer at the published
size) are drawn when a layer is evaluated and dropped after it, so the
reference fits beside nothing on the chip.
"""

from __future__ import annotations

import math
import sys
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
#: the store's lanes: the latent row is kept as planes of this width, the
#: rotary key padded to it (the configuration's ``engine.kv``)
LANES = 128


class Dims(NamedTuple):
    vocab: int
    d: int
    heads: int
    nope: int
    rope: int
    v_head: int
    q_rank: int
    kv_rank: int
    d_ff: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int
    n_dense: int
    n_layers: int
    route_scale: float
    theta: float
    eps: float


def dims_of(cfg: Dict[str, Any]) -> Dims:
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("the reference routes over one group only")
    if not cfg.get("norm_topk_prob", True) or cfg.get("attention_bias"):
        raise ValueError("the reference normalises the picked scores and "
                         "has no attention bias")
    return Dims(
        vocab=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_head=int(cfg["v_head_dim"]), q_rank=int(cfg["q_lora_rank"]),
        kv_rank=int(cfg["kv_lora_rank"]), d_ff=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_experts=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg["n_shared_experts"]),
        n_dense=int(cfg["first_k_dense_replace"]),
        n_layers=int(cfg["num_hidden_layers"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


# --------------------------------------------------------------------------- #
# weights: the configuration's recipe
# --------------------------------------------------------------------------- #

#: a layer's matrices in the order the recipe numbers them; a dense layer
#: has the first eight, an expert layer all but the dense MLP's three
ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
DENSE = ("w_gate", "w_up", "w_down")
MOE = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


def leaf_shapes(m: Dims, layer: int) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of the matrices of ``layer``, each (.., in, out)."""
    h, f = m.heads, m.d_expert
    out = {"wq_a": (m.d, m.q_rank),
           "wq_b": (m.q_rank, h * (m.nope + m.rope)),
           "wkv_a": (m.d, m.kv_rank + m.rope),
           "wkv_b": (m.kv_rank, h * (m.nope + m.v_head)),
           "wo": (h * m.v_head, m.d)}
    if layer < m.n_dense:
        out.update(w_gate=(m.d, m.d_ff), w_up=(m.d, m.d_ff),
                   w_down=(m.d_ff, m.d))
    else:
        out.update(router=(m.d, m.n_experts),
                   e_gate=(m.n_experts, m.d, f), e_up=(m.n_experts, m.d, f),
                   e_down=(m.n_experts, f, m.d),
                   s_gate=(m.d, f * m.n_shared), s_up=(m.d, f * m.n_shared),
                   s_down=(f * m.n_shared, m.d))
    return out


@partial(jax.jit, static_argnames=("shape", "scale"))
def _draw(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def draw_leaf(seed: int, m: Dims, layer: int, name: str) -> jax.Array:
    """The recipe: leaf number j of layer l is N(0, 1/fan_in) under
    ``fold_in(fold_in(PRNGKey(seed), l), j)``, j its place in ``ATTN +
    DENSE`` or ``ATTN + MOE``; ``embed`` and ``head`` are leaves 0 and 1 of
    "layer" ``num_hidden_layers``, N(0, 0.02^2)."""
    base = jax.random.PRNGKey(int(seed))
    if name in ("embed", "head"):
        key = jax.random.fold_in(jax.random.fold_in(base, m.n_layers),
                                 ("embed", "head").index(name))
        return _draw(key, (m.vocab, m.d), 0.02)
    names = ATTN + (DENSE if layer < m.n_dense else MOE)
    shape = leaf_shapes(m, layer)[name]
    key = jax.random.fold_in(jax.random.fold_in(base, layer),
                             names.index(name))
    return _draw(key, shape, 1.0 / math.sqrt(shape[-2]))


# --------------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------------- #

def _split(x):
    """A float32 array as two bfloat16 pieces, x = hi + lo to 16 bits.
    ``reduce_precision`` and not a cast there and back, which XLA is free
    to drop (``xla_allow_excess_precision``)."""
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotary(x, theta):
    """Rotary position on the last axis of x (T, .., r), row t at position
    t; the two halves of the axis are the pairs."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "high"))
def _attention(h, w, m: Dims, high):
    """The attention half of a block over a whole sequence (T, D). Returns
    the residual's new value, the normed latent rows (T, C) and the rotary
    keys (T, r): what a latent store holds."""
    t = h.shape[0]
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    a = _rms(h, 1.0, m.eps)        # norm scales are one (the recipe)
    q = mm(_rms(mm(a, w["wq_a"]), 1.0, m.eps), w["wq_b"]).reshape(
        t, m.heads, m.nope + m.rope)
    qn, qr = q[..., :m.nope], _rotary(q[..., m.nope:], m.theta)
    kv = mm(a, w["wkv_a"])
    c = _rms(kv[:, :m.kv_rank], 1.0, m.eps)
    kr = _rotary(kv[:, m.kv_rank:], m.theta)
    kvb = mm(c, w["wkv_b"]).reshape(t, m.heads, m.nope + m.v_head)
    kn, v = kvb[..., :m.nope], kvb[..., m.nope:]
    s = (_contract("qhd,khd->hqk", qn, kn, high)
         + _contract("qhr,kr->hqk", qr, kr, high)) \
        / math.sqrt(m.nope + m.rope)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    o = _contract("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v, high)
    return h + mm(o.reshape(t, -1), w["wo"]), c, kr


@partial(jax.jit, static_argnames=("m", "high"))
def _dense_mlp(h, w, m: Dims, high):
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    x = _rms(h, 1.0, m.eps)
    return h + mm(_silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]), w["w_down"])


@partial(jax.jit, static_argnames=("m", "high"))
def _route(h, router, bias, second, m: Dims, high):
    """Picks of every row: ``(weight (T, E), tie (T,))``. ``weight`` is 0
    where a row did not pick the expert. A row of ``second`` takes its
    second pick: the first unpicked score in place of the last picked.
    ``tie`` is how far apart those two selection scores lie."""
    x = _rms(h, 1.0, m.eps)
    s = jax.nn.sigmoid(_contract("td,de->te", x, router, high))
    vals, idx = jax.lax.top_k(s + bias, m.top_k + 1)
    last = jnp.where(second, idx[:, m.top_k], idx[:, m.top_k - 1])
    idx = jnp.concatenate([idx[:, :m.top_k - 1], last[:, None]], axis=1)
    tops = jnp.take_along_axis(s, idx, axis=1)
    w = tops / jnp.sum(tops, -1, keepdims=True) * m.route_scale
    onehot = idx[:, :, None] == jnp.arange(m.n_experts)[None, None]
    weight = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)
    return weight, vals[:, m.top_k - 1] - vals[:, m.top_k]


@partial(jax.jit, static_argnames=("m", "high"))
def _experts(h, weight, w, m: Dims, high):
    """``h + sum_e weight[:, e] E_e(x) + E_shared(x)``: a loop over all
    experts, every row through each."""
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    x = _rms(h, 1.0, m.eps)

    def one(y, ew):
        wg, wu, wd, we = ew
        return y + we[:, None] * mm(_silu(mm(x, wg)) * mm(x, wu), wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["e_gate"], w["e_up"], w["e_down"], weight.T))
    shared = mm(_silu(mm(x, w["s_gate"])) * mm(x, w["s_up"]), w["s_down"])
    return h + y + shared


@partial(jax.jit, static_argnames=("m", "high"))
def _score(h, head, query, m: Dims, high):
    """For each row: how far the queried token's logit lies below the
    best, and the best token."""
    logits = _contract("td,vd->tv", _rms(h, 1.0, m.eps), head, high)
    got = jnp.take_along_axis(logits, query[:, None], axis=-1)[:, 0]
    return logits.max(-1) - got, jnp.argmax(logits, -1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("m", "high"))
def _logits(h, head, m: Dims, high):
    return _contract("td,vd->tv", _rms(h, 1.0, m.eps), head, high)


@jax.jit
def _rel_gap(got, want, rows):
    """Over the first ``rows`` rows of (T, width) arrays: the norm of the
    difference over the norm of what was wanted."""
    live = (jnp.arange(want.shape[0]) < rows)[:, None]
    return jnp.linalg.norm(jnp.where(live, got - want, 0.0).ravel()) \
        / jnp.linalg.norm(jnp.where(live, want, 0.0).ravel())


def to_store(c: np.ndarray, kr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """One layer's latent rows (rows, C) and rotary keys (rows, r) in the
    store's layout: planes (C / 128, rows, 128), and (1, rows, 128)."""
    rows, width = c.shape
    planes = c.reshape(rows, width // LANES, LANES).transpose(1, 0, 2)
    pad = np.zeros((1, rows, LANES), np.float32)
    pad[0, :, :kr.shape[1]] = kr
    return planes, pad


def from_store(kv, layer: int, m: Dims, rows: int, tp: int):
    """The inverse for ``layer``, padded with zero rows to ``tp``."""
    g = m.kv_rank // LANES
    c = np.zeros((tp, m.kv_rank), np.float32)
    c[:rows] = kv[0][layer * g:(layer + 1) * g, :rows].transpose(
        1, 0, 2).reshape(rows, m.kv_rank)
    kr = np.zeros((tp, m.rope), np.float32)
    kr[:rows] = kv[1][layer, :rows, :m.rope]
    return c, kr


class Reference:
    """Weights from the seed, then ``score`` over served sequences."""

    def __init__(self, cfg: Dict[str, Any], seed: int,
                 mode: str = "reference") -> None:
        if mode not in ("reference", "control"):
            raise ValueError(f"unknown mode {mode!r}")
        if cfg.get("quantize"):
            raise ValueError("this family has no quantized form")
        self.cfg, self.seed = cfg, int(seed)
        self.m = dims_of(cfg)
        self.high = mode == "control"
        self.route_tie = float(cfg["route_tie"])
        m = self.m
        self.embed = draw_leaf(seed, m, 0, "embed")
        self.head = draw_leaf(seed, m, 0, "head")
        #: what stays on the device between sequences: everything but the
        #: routed experts
        self.layers: List[Dict[str, jax.Array]] = [
            {k: draw_leaf(seed, m, li, k) for k in leaf_shapes(m, li)
             if not k.startswith("e_")} for li in range(m.n_layers)]
        self.bias = jnp.zeros((m.n_experts,), jnp.float32)
        self.tied = self.took_second = 0

    # -- one sequence ----------------------------------------------------- #

    def _layer(self, li: int, h, second):
        """Layer ``li`` over h (T, D); ``second`` (T,) bool marks the rows
        that take their second pick. Returns (h, c, kr, tie or None)."""
        m, w = self.m, self.layers[li]
        h, c, kr = _attention(h, w, m, self.high)
        if li < m.n_dense:
            return _dense_mlp(h, w, m, self.high), c, kr, None
        weight, tie = _route(h, w["router"], self.bias, second, m, self.high)
        experts = {k: draw_leaf(self.seed, m, li, k)
                   for k in ("e_gate", "e_up", "e_down")}
        h = _experts(h, weight, {**w, **experts}, m, self.high)
        return h, c, kr, tie

    def _forward(self, h, start: int, flips: Dict[int, np.ndarray],
                 rows: int, kv):
        """Layers ``start ..`` over h. Returns (final h, and for each of
        those layers: its input, its own (c, kr), its kv gap (0.0 without
        ``kv``), the rows whose two selection scores lie within
        ``route_tie``)."""
        tp = h.shape[0]
        inputs, kept, gaps, ties = [], [], [], []
        for li in range(start, self.m.n_layers):
            inputs.append(h)
            second = flips.get(li, np.zeros((tp,), bool))
            h, c, kr, tie = self._layer(li, h, jnp.asarray(second))
            ties.append([] if tie is None else [
                int(r) for r in np.flatnonzero(
                    np.asarray(tie)[:rows] < self.route_tie)])
            gap = 0.0
            if kv is not None:
                for mine, got in zip((c, kr),
                                     from_store(kv, li, self.m, rows, tp)):
                    gap = max(gap, float(_rel_gap(jnp.asarray(got), mine,
                                                  rows)))
            gaps.append(gap)
            kept.append((c, kr))
        return h, inputs, kept, gaps, ties

    def _pad(self, seq: np.ndarray):
        rows = int(seq.size)
        tp = 128
        while tp < rows:
            tp *= 2
        ids = np.zeros((tp,), np.int32)
        ids[:rows] = seq
        return ids, rows, tp

    def logits(self, tokens: Sequence[int]) -> np.ndarray:
        """The full forward pass over ``tokens``: (T, vocab) logits, first
        picks everywhere (tests)."""
        ids, rows, _ = self._pad(np.asarray(tokens, np.int32))
        h = self._forward(self.embed[jnp.asarray(ids)], 0, {}, rows,
                          None)[0]
        return np.asarray(_logits(h, self.head, self.m, self.high))[:rows]

    def score(self, prompt: np.ndarray, served: Sequence[int],
              query: Optional[Sequence[int]] = None,
              kv: Optional[Tuple[np.ndarray, np.ndarray]] = None,
              keep_kv: bool = False):
        """One forward over the prompt and what was served after it
        (teacher-forced). Returns, for each served position, how far the
        logit of ``query``'s token (default: the served token) lies below
        this model's best there, and this model's best token; then, given
        ``kv`` (the rows some other arithmetic holds for the same sequence,
        in the store's layout), the widest relative gap of a layer's latent
        rows or rotary keys from this model's; then, with ``keep_kv``, this
        model's own rows in that layout."""
        served = np.asarray(served, np.int32)
        query = served if query is None else np.asarray(query, np.int32)
        n, t = int(served.size), int(prompt.size)
        ids, rows, tp = self._pad(np.concatenate(
            [np.asarray(prompt, np.int32), served[:-1]]))
        want = np.zeros((tp,), np.int32)
        want[t - 1:t - 1 + n] = query
        at = slice(t - 1, t - 1 + n)

        def run(h, start, flips):
            h, inputs, kept, gaps, ties = self._forward(h, start, flips,
                                                        rows, kv)
            gap, best = _score(h, self.head, jnp.asarray(want), self.m,
                               self.high)
            return {"gap": np.asarray(gap)[at], "best": np.asarray(best)[at],
                    "inputs": inputs, "kept": kept, "gaps": gaps,
                    "ties": ties}

        def far(state, li, row):
            """How far a state lies from what it is compared with, as the
            pick at (``li``, ``row``) decides it. With the store's rows
            and a layer behind ``li``: that token's own rows in the next
            layer, which depend on this pick and on picks already gone
            with, and on no other. Else the served tokens' gaps, summed
            (a maximum would hide this pick behind another's)."""
            if kv is None or li + 1 == self.m.n_layers:
                return float(np.sum(state["gap"]))
            theirs = from_store(kv, li + 1, self.m, rows, tp)
            return max(
                float(np.linalg.norm(np.asarray(mine[row]) - got[row])
                      / np.linalg.norm(got[row]))
                for mine, got in zip(state["kept"][li + 1], theirs))

        flips: Dict[int, np.ndarray] = {}
        now = run(self.embed[jnp.asarray(ids)], 0, flips)
        decided = set()
        while True:
            open_ties = sorted({(li, r) for li, rs in enumerate(now["ties"])
                                for r in rs} - decided)
            if not open_ties:
                break
            li, row = open_ties[0]
            decided.add((li, row))
            self.tied += 1
            trial = {k: v.copy() for k, v in flips.items()}
            trial.setdefault(li, np.zeros((tp,), bool))[row] = True
            other = run(now["inputs"][li], li, trial)
            # the layers before ``li`` are the same under both picks
            for key in ("inputs", "kept", "gaps", "ties"):
                other[key] = now[key][:li] + other[key]
            if far(other, li, row) < far(now, li, row):
                self.took_second += 1
                flips, now = trial, other
        out = (now["gap"], now["best"])
        if kv is not None:
            out += (max(now["gaps"]),)
        if keep_kv:
            pairs = [to_store(np.asarray(c)[:rows], np.asarray(kr)[:rows])
                     for c, kr in now["kept"]]
            out += ((np.concatenate([p[0] for p in pairs]),
                     np.concatenate([p[1] for p in pairs])),)
        return out

    def free(self) -> None:
        print(f"compared route_ties: {self.tied} token-layers within "
              f"{self.route_tie!r}, {self.took_second} went with the second "
              "pick", file=sys.stderr)
        self.embed = self.head = self.layers = None
