"""Plain reference of Qwen3-Next's block (``qwen3_next``) as the
configuration states it, for the experts this chip holds.

Straightforward ``jax.numpy`` in float32 with every contraction at
``highest``: no cache, no batching, no kernels; the gated delta rule in its
sequential form, token after token (``torch_recurrent_gated_delta_rule``);
naive grouped attention; a loop over the held experts. It imports nothing
of the program and takes nothing the program made: the weights are drawn
here from the seed by the recipe the configuration file states
(``weights``).

Layer equations (x a row; the norms before the mixer, before the experts
and before the head are zero-centred, ``x rsqrt(mean x^2 + eps) (1 + w)``):

* layer l is full attention where ``(l + 1) % full_attention_interval`` is
  0, else Gated DeltaNet.
* Gated DeltaNet: ``[q k v z] = x W_qkvz`` (q, k: ``linear_num_key_heads``
  heads of ``linear_key_head_dim``; v, z: ``linear_num_value_heads`` of
  ``linear_value_head_dim``), ``[b a] = x W_ba``; ``[q k v]`` through the
  causal depthwise convolution of width ``linear_conv_kernel_dim``, then
  SiLU; q, k L2-normalised a head (eps 1e-6), q scaled by ``dk ** -0.5``;
  key head ``h // (H / Hk)`` serves value head h; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; from a zero state, each token:
  ``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q``;
  ``out = W_out(RMSNorm(o) w_n silu(z))`` (this norm is not zero-centred).
* full attention: ``[q gate] = x W_q`` a head (``head_dim`` each); q and k
  normed a head (zero-centred); rotary (``rope_theta``) on the first
  ``head_dim * partial_rotary_factor`` dims, the two halves of those dims
  paired; ``num_attention_heads / num_key_value_heads`` query heads over a
  K/V head; ``o = softmax(q k / sqrt(head_dim)) v``, causal;
  ``out = W_o(o sigmoid(gate))``.
* experts: ``p = softmax(x W_r)`` over all the router's experts; the
  ``num_experts_per_tok`` largest, over their sum; ``y = sum w_e E_e(x)``
  over the picks of the experts held here, ``+ sigmoid(x w_sg)
  E_shared(x)``, every E a gated SiLU MLP. A pick of an expert held
  elsewhere adds nothing: this is one chip's share of the layer.

``mode`` selects the arithmetic: ``"reference"`` is what the configuration
states; ``"control"`` is the nearest precision below it, ``high`` (three
bfloat16 passes a1.b1 + a1.b2 + a2.b1 with float32 accumulation, written
out so that it is the same arithmetic on any backend), in every
contraction, the delta rule's among them. ``correct`` has to come out false
for the control.

**Routing ties (``route_tie``).** As in ``glm47_flash.py``: where a row's
last picked and first unpicked scores (softmax probabilities) lie within
``route_tie``, both picks are the model; the reference evaluates both, in
the order of layers and rows, and goes on with the one under which what it
compares lies closer: where the store's rows are given, that token's own K
and V rows in the first full-attention layer after the tie (the layers
past it are run for the pick taken alone); else the served tokens' gaps,
summed, and a tie that no gap above zero lies behind is decided without a
second forward (no pick can lower a sum of zeros). ``free()`` prints how
many token-layers were tied and how many went with the second pick.

The forward pass runs layer by layer, one sequence at a time, padded to the
configuration's ``engine.max_len`` (else to a power of two), so that each
function compiles once; rows past the sequence are hidden by the causal
mask, and the recurrence stops at the last real row. Every weight, the
held experts' included, is drawn at construction and kept until
``free()``: the reference holds the chip alone (the engine is freed before
the check).
"""

from __future__ import annotations

import math
import sys
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary: int
    key_heads: int
    value_heads: int
    dk: int
    dv: int
    taps: int
    interval: int
    f: int
    fs: int
    first: int          # the first expert held here
    held: int           # experts held here
    experts: int        # experts the router scores
    top_k: int
    n_layers: int
    theta: float
    eps: float


def dims_of(cfg: Dict[str, Any]) -> Dims:
    if not cfg.get("norm_topk_prob", True) or cfg.get("mlp_only_layers"):
        raise ValueError("the reference normalises the picked scores and "
                         "has experts in every layer")
    dep = cfg.get("deployment", {})
    hd = int(cfg["head_dim"])
    return Dims(
        vocab=int(cfg["vocab_size"]), d=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]), head_dim=hd,
        rotary=int(round(hd * float(cfg["partial_rotary_factor"]))),
        key_heads=int(cfg["linear_num_key_heads"]),
        value_heads=int(cfg["linear_num_value_heads"]),
        dk=int(cfg["linear_key_head_dim"]),
        dv=int(cfg["linear_value_head_dim"]),
        taps=int(cfg["linear_conv_kernel_dim"]),
        interval=int(cfg["full_attention_interval"]),
        f=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["shared_expert_intermediate_size"]),
        first=int(dep.get("experts_held", [0])[0]),
        held=int(cfg["num_experts"]),
        experts=int(dep.get("router_experts", cfg["num_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        n_layers=int(cfg["num_hidden_layers"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def is_full(m: Dims, layer: int) -> bool:
    return (layer + 1) % m.interval == 0


# --------------------------------------------------------------------------- #
# weights: the configuration's recipe
# --------------------------------------------------------------------------- #

LINEAR = ("w_qkvz", "w_ba", "conv", "w_out")
FULL = ("wq", "wk", "wv", "wo")
MOE = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
       "s_weigh")
#: the shared expert's leaves, which ``_experts`` takes beside the routed
SHARED = ("s_gate", "s_up", "s_down", "s_weigh")


def leaf_shapes(m: Dims, full: bool) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of the matrices of a full-attention or a Gated
    DeltaNet layer, each (.., in, out); a routed expert's three are one
    expert's."""
    out = {"router": (m.d, m.experts), "e_gate": (m.d, m.f),
           "e_up": (m.d, m.f), "e_down": (m.f, m.d), "s_gate": (m.d, m.fs),
           "s_up": (m.d, m.fs), "s_down": (m.fs, m.d), "s_weigh": (m.d, 1)}
    if full:
        out.update(wq=(m.d, m.heads * 2 * m.head_dim),
                   wk=(m.d, m.kv_heads * m.head_dim),
                   wv=(m.d, m.kv_heads * m.head_dim),
                   wo=(m.heads * m.head_dim, m.d))
    else:
        c = 2 * m.key_heads * m.dk + m.value_heads * m.dv
        out.update(w_qkvz=(m.d, c + m.value_heads * m.dv),
                   w_ba=(m.d, 2 * m.value_heads), conv=(m.taps, c),
                   w_out=(m.value_heads * m.dv, m.d))
    return out


# The three draws are written as the builder writes them, under the same
# names: the same programs, which the persistent compile cache then holds
# from set-up, so that a check compiles none of them.

@partial(jax.jit, static_argnames=("shape", "scale"))
def _draw(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


@partial(jax.jit, static_argnames=("shape", "first", "count"))
def _draw_experts(key, shape, first, count):
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
        jnp.arange(first, first + count))
    return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(
        keys) / math.sqrt(shape[-2])


@partial(jax.jit, static_argnames=("n",))
def _a_log(key, n):
    return jnp.log(jax.random.uniform(key, (n,), jnp.float32, 1.0, 16.0))


def draw_layer(seed: int, m: Dims, layer: int) -> Dict[str, jax.Array]:
    """The recipe for layer ``layer``: leaf number j is N(0, 1/fan_in)
    under ``fold_in(fold_in(PRNGKey(seed), layer), j)``, j its place in
    ``FULL + MOE`` or ``LINEAR + MOE``; routed expert e's matrices under
    one more ``fold_in(.., e)``, the held ones stacked; a linear layer's
    ``A_log`` is leaf ``len(LINEAR + MOE)``, the log of U(1, 16)."""
    full = is_full(m, layer)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), layer)
    names = (FULL if full else LINEAR) + MOE
    shapes = leaf_shapes(m, full)
    out = {}
    for j, name in enumerate(names):
        leaf, shape = jax.random.fold_in(key, j), shapes[name]
        out[name] = _draw_experts(leaf, shape, m.first, m.held) \
            if name.startswith("e_") \
            else _draw(leaf, shape, 1.0 / math.sqrt(shape[-2]))
    if not full:
        out["A_log"] = _a_log(jax.random.fold_in(key, len(names)),
                              m.value_heads)
    return out


def draw_vocab(seed: int, m: Dims) -> Tuple[jax.Array, jax.Array]:
    """``embed`` and ``head``: leaves 0 and 1 of "layer"
    ``num_hidden_layers``, N(0, 0.02^2)."""
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), m.n_layers)
    return tuple(_draw(jax.random.fold_in(key, j), (m.vocab, m.d), 0.02)
                 for j in (0, 1))


# --------------------------------------------------------------------------- #
# arithmetic
# --------------------------------------------------------------------------- #

def _split(x):
    """A float32 array as two bfloat16 pieces, x = hi + lo to 16 bits."""
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


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


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
def _linear(h, w, a_log, rows, m: Dims, high):
    """A Gated DeltaNet layer's mixer over a whole sequence (T, D), added
    to the residual. Norm scales are as the recipe sets them: the
    zero-centred ones zero, the gated one one. The recurrence runs over
    the first ``rows`` rows; a padding row's mixer adds nothing."""
    t = h.shape[0]
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    x = _rms(h, m.eps)
    qkvz, ba = mm(x, w["w_qkvz"]), mm(x, w["w_ba"])
    c = 2 * m.key_heads * m.dk + m.value_heads * m.dv
    pad = jnp.concatenate([jnp.zeros((m.taps - 1, c)), qkvz[:, :c]])
    mixed = _silu(sum(pad[j:j + t] * w["conv"][j] for j in range(m.taps)))
    rep = m.value_heads // m.key_heads

    def heads(z, n, dim):
        return z.reshape(t, n, dim)

    def l2(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(heads(mixed[:, :m.key_heads * m.dk], m.key_heads,
                            m.dk)), rep, axis=1) * m.dk ** -0.5
    k = jnp.repeat(l2(heads(mixed[:, m.key_heads * m.dk:
                                  2 * m.key_heads * m.dk],
                            m.key_heads, m.dk)), rep, axis=1)
    v = heads(mixed[:, 2 * m.key_heads * m.dk:], m.value_heads, m.dv)
    z = heads(qkvz[:, c:], m.value_heads, m.dv)
    beta = jax.nn.sigmoid(ba[:, :m.value_heads])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, m.value_heads:] + 1.0)

    def token(i, carry):
        s, o = carry
        qt, kt, vt, gt, bt = (z[i] for z in (q, k, v, g, beta))
        s = s * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - _contract("hk,hkv->hv", kt, s, high))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, o.at[i].set(_contract("hk,hkv->hv", qt, s, high))

    _, o = jax.lax.fori_loop(
        0, rows, token, (jnp.zeros((m.value_heads, m.dk, m.dv)),
                         jnp.zeros((t, m.value_heads, m.dv))))
    o = _rms(o, m.eps) * _silu(z)
    return h + mm(o.reshape(t, -1), w["w_out"])


@partial(jax.jit, static_argnames=("m", "high"))
def _full(h, w, m: Dims, high):
    """A full-attention layer over a whole sequence (T, D), added to the
    residual; and its K and V rows after norm and rotary (T, Hkv, hd)."""
    t = h.shape[0]
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    x = _rms(h, m.eps)
    qg = mm(x, w["wq"]).reshape(t, m.heads, 2 * m.head_dim)
    q, gate = _rms(qg[..., :m.head_dim], m.eps), qg[..., m.head_dim:]
    k = _rms(mm(x, w["wk"]).reshape(t, m.kv_heads, m.head_dim), m.eps)
    v = mm(x, w["wv"]).reshape(t, m.kv_heads, m.head_dim)
    q, k = (jnp.concatenate([_rotary(z[..., :m.rotary], m.theta),
                             z[..., m.rotary:]], -1) for z in (q, k))
    group = m.heads // m.kv_heads
    kq, vq = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _contract("qhd,khd->hqk", q, kq, high) / math.sqrt(m.head_dim)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    o = _contract("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vq, high)
    o = o * jax.nn.sigmoid(gate)
    return h + mm(o.reshape(t, -1), w["wo"]), k, v


@partial(jax.jit, static_argnames=("m", "high"))
def _route(h, router, second, m: Dims, high):
    """Picks of every row among the held experts: ``(weight (T, held), tie
    (T,))``. A row of ``second`` takes its second pick: the first unpicked
    score in place of the last picked. ``tie`` is how far apart those two
    scores lie."""
    p = jax.nn.softmax(_contract("td,de->te", _rms(h, m.eps), router, high),
                       axis=-1)
    vals, idx = jax.lax.top_k(p, m.top_k + 1)
    last = jnp.where(second, idx[:, m.top_k], idx[:, m.top_k - 1])
    idx = jnp.concatenate([idx[:, :m.top_k - 1], last[:, None]], axis=1)
    tops = jnp.take_along_axis(p, idx, axis=1)
    w = tops / jnp.sum(tops, -1, keepdims=True)
    onehot = idx[:, :, None] == (m.first + jnp.arange(m.held))[None, None]
    weight = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)
    return weight, vals[:, m.top_k - 1] - vals[:, m.top_k]


@partial(jax.jit, static_argnames=("m", "high"))
def _experts(h, weight, w, m: Dims, high):
    """``h + sum_e weight[:, e] E_e(x) + sigmoid(x w_sg) E_shared(x)``: a
    loop over the held experts, every row through each."""
    mm = lambda x, y: _contract("td,df->tf", x, y, high)     # noqa: E731
    x = _rms(h, m.eps)

    def one(y, ew):
        wg, wu, wd, we = ew
        return y + we[:, None] * mm(_silu(mm(x, wg)) * mm(x, wu), wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["e_gate"], w["e_up"], w["e_down"], weight.T))
    shared = mm(_silu(mm(x, w["s_gate"])) * mm(x, w["s_up"]), w["s_down"])
    return h + y + jax.nn.sigmoid(mm(x, w["s_weigh"])) * shared


@partial(jax.jit, static_argnames=("m", "high"))
def _score(h, head, query, m: Dims, high):
    """For each row: how far the queried token's logit lies below the
    best, and the best token."""
    logits = _contract("td,vd->tv", _rms(h, m.eps), head, high)
    got = jnp.take_along_axis(logits, query[:, None], axis=-1)[:, 0]
    return logits.max(-1) - got, jnp.argmax(logits, -1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("m", "high"))
def _logits(h, head, m: Dims, high):
    return _contract("td,vd->tv", _rms(h, m.eps), head, high)


@jax.jit
def _embed(table, ids):
    return table[ids]


@jax.jit
def _row(z, i):
    return z[i]


@jax.jit
def _rel_gap(got, want, rows):
    """Over the first ``rows`` rows of (T, ..) arrays: the norm of the
    difference over the norm of what was wanted."""
    live = (jnp.arange(want.shape[0]) < rows).reshape(
        (-1,) + (1,) * (want.ndim - 1))
    return jnp.linalg.norm(jnp.where(live, got - want, 0.0).ravel()) \
        / jnp.linalg.norm(jnp.where(live, want, 0.0).ravel())


def from_store(kv, full: int, m: Dims, rows: int, tp: int):
    """The K and V rows of the ``full``-th full-attention layer out of the
    store's layout ((L_full * Hkv, rows, hd) each), as (tp, Hkv, hd) padded
    with zero rows."""
    out = []
    for part in kv:
        z = np.zeros((tp, m.kv_heads, m.head_dim), np.float32)
        z[:rows] = part[full * m.kv_heads:(full + 1) * m.kv_heads,
                        :rows].transpose(1, 0, 2)
        out.append(z)
    return out


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
        self.embed, self.head = draw_vocab(self.seed, m)
        #: each layer's leaves, and apart from them its held experts, all
        #: kept on the device until ``free()``: the reference holds the chip
        #: alone, and a tie's second forward goes through the same layers
        self.layers: List[Dict[str, jax.Array]] = []
        self.held: List[Dict[str, jax.Array]] = []
        for li in range(m.n_layers):
            w = draw_layer(self.seed, m, li)
            self.held.append({k: w.pop(k)
                              for k in ("e_gate", "e_up", "e_down")})
            self.layers.append(w)
        #: every sequence is padded to one length, so that each function
        #: compiles once for a check
        self.pad_to = int(cfg.get("engine", {}).get("max_len", 0))
        self.tied = self.took_second = 0
        if self.pad_to:
            self.compile_check(self.pad_to)

    def compile_check(self, tp: int) -> None:
        """Compile the programs a check calls over ``tp`` padded rows side
        by side, before the first sequence: the accelerator's compiler runs
        outside the interpreter's lock, and five programs of a cold check
        took 51.5 s one after another and 16.9 s side by side on a TPU v5e.
        The calls that follow find them compiled."""
        m, high = self.m, self.high
        kinds = {is_full(m, li): (li, w) for li, w in enumerate(self.layers)}
        shape = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        h = shape((tp, m.d))
        kv = shape((tp, m.kv_heads, m.head_dim))
        index = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)
        li, w = next(iter(kinds.values()))
        jobs = [
            lambda: _route.lower(h, w["router"],
                                 jax.ShapeDtypeStruct((tp,), jnp.bool_),
                                 m, high),
            lambda: _experts.lower(h, shape((tp, m.held)),
                                   {**{k: w[k] for k in SHARED},
                                    **self.held[li]}, m, high),
            lambda: _score.lower(h, self.head,
                                 jax.ShapeDtypeStruct((tp,), jnp.int32),
                                 m, high),
            lambda: _embed.lower(self.embed,
                                 jax.ShapeDtypeStruct((tp,), jnp.int32)),
        ]
        if False in kinds:
            lin = kinds[False][1]
            jobs.append(lambda: _linear.lower(
                h, lin, lin["A_log"], jax.ShapeDtypeStruct((), jnp.int32),
                m, high))
        if True in kinds:
            jobs += [lambda: _full.lower(h, kinds[True][1], m, high),
                     lambda: _rel_gap.lower(kv, kv, index),
                     lambda: _row.lower(kv, index)]
        with ThreadPoolExecutor(len(jobs)) as pool:
            list(pool.map(lambda job: job().compile(), jobs))

    def experts(self, li: int) -> Dict[str, jax.Array]:
        """The held experts' three matrices of layer ``li``, stacked."""
        return self.held[li]

    # -- one sequence ----------------------------------------------------- #

    def _layer(self, li: int, h, second, rows: int):
        """Layer ``li`` over h (T, D), of which ``rows`` are the sequence;
        ``second`` (T,) bool marks the rows that take their second pick.
        Returns (h, (k, v) or None, tie)."""
        m, w = self.m, self.layers[li]
        kv = None
        if is_full(m, li):
            h, k, v = _full(h, w, m, self.high)
            kv = (k, v)
        else:
            h = _linear(h, w, w["A_log"], jnp.int32(rows), m, self.high)
        weight, tie = _route(h, w["router"], second, m, self.high)
        h = _experts(h, weight, {**{k: w[k] for k in SHARED},
                                 **self.experts(li)}, m, self.high)
        return h, kv, tie

    def _forward(self, h, start: int, flips: Dict[int, np.ndarray],
                 rows: int, kv, stop: Optional[int] = None):
        """Layers ``start`` up to ``stop`` (default: the last) over h.
        Returns (h after them, and for each of those layers: its input, its
        own (k, v) or None, its kv gap (0.0 without ``kv`` or for a linear
        layer), the rows whose two scores lie within ``route_tie``)."""
        tp = h.shape[0]
        inputs, kept, gaps, ties = [], [], [], []
        for li in range(start, self.m.n_layers if stop is None else stop):
            inputs.append(h)
            second = flips.get(li, np.zeros((tp,), bool))
            h, mine, tie = self._layer(li, h, jnp.asarray(second), rows)
            ties.append([int(r) for r in np.flatnonzero(
                np.asarray(tie)[:rows] < self.route_tie)])
            gap = 0.0
            if kv is not None and mine is not None:
                got = from_store(kv, li // self.m.interval, self.m, rows, tp)
                gap = max(float(_rel_gap(jnp.asarray(g), z, rows))
                          for g, z in zip(got, mine))
            gaps.append(gap)
            kept.append(mine)
        return h, inputs, kept, gaps, ties

    def _pad(self, seq: np.ndarray):
        rows = int(seq.size)
        tp = self.pad_to if rows <= self.pad_to else 128
        while tp < rows:
            tp *= 2
        ids = np.zeros((tp,), np.int32)
        ids[:rows] = seq
        return ids, rows, tp

    def logits(self, tokens: Sequence[int]) -> np.ndarray:
        """The full forward pass over ``tokens``: (T, vocab) logits, first
        picks everywhere (tests)."""
        ids, rows, _ = self._pad(np.asarray(tokens, np.int32))
        h = self._forward(_embed(self.embed, jnp.asarray(ids)), 0, {}, rows,
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
        ``kv`` (the full layers' K and V rows some other arithmetic holds
        for the same sequence, in the store's layout), the widest relative
        gap of a layer's K or V rows from this model's; then, with
        ``keep_kv``, this model's own rows in that layout."""
        served = np.asarray(served, np.int32)
        query = served if query is None else np.asarray(query, np.int32)
        n, t = int(served.size), int(prompt.size)
        ids, rows, tp = self._pad(np.concatenate(
            [np.asarray(prompt, np.int32), served[:-1]]))
        want = np.zeros((tp,), np.int32)
        want[t - 1:t - 1 + n] = query
        at = slice(t - 1, t - 1 + n)

        lists = ("inputs", "kept", "gaps", "ties")

        def run(h, start, flips, stop=self.m.n_layers):
            h, inputs, kept, gaps, ties = self._forward(h, start, flips,
                                                        rows, kv, stop)
            state = {"h": h, "inputs": inputs, "kept": kept, "gaps": gaps,
                     "ties": ties}
            if stop == self.m.n_layers:
                gap, best = _score(h, self.head, jnp.asarray(want), self.m,
                                   self.high)
                state.update(gap=np.asarray(gap)[at],
                             best=np.asarray(best)[at])
            return state

        def judge(li):
            """The full-attention layer whose stored rows judge a pick in
            layer ``li``: the first after it, given the store's rows; else
            None, and the served tokens' gaps judge."""
            return None if kv is None else next(
                (j for j in range(li + 1, self.m.n_layers)
                 if is_full(self.m, j)), None)

        def far(state, li, row):
            """How far a state lies from what it is compared with, as the
            pick at (``li``, ``row``) decides it: that token's own K and V
            rows in ``judge(li)``'s layer (they depend on this pick, on
            picks already gone with, and on first picks at rows up to
            ``row`` in the layers between, the same under both picks);
            else the served tokens' gaps, summed."""
            nxt = judge(li)
            if nxt is None:
                return float(np.sum(state["gap"]))
            theirs = from_store(kv, nxt // self.m.interval, self.m, rows, tp)
            return max(
                float(np.linalg.norm(np.asarray(_row(mine, row)) - got[row])
                      / np.linalg.norm(got[row]))
                for mine, got in zip(state["kept"][nxt], theirs))

        def moot(li, row):
            """True where the second pick cannot lie closer: the gaps judge
            it, and none is above zero at a position the pick reaches (a
            pick at ``row`` moves the logits of rows from ``row`` on; served
            position i is row t - 1 + i). The forward is then not run."""
            return judge(li) is None \
                and not np.any(now["gap"][max(0, row - t + 1):] > 0)

        flips: Dict[int, np.ndarray] = {}
        now = run(_embed(self.embed, jnp.asarray(ids)), 0, flips)
        decided = set()
        while True:
            open_ties = sorted({(li, r) for li, rs in enumerate(now["ties"])
                                for r in rs} - decided)
            if not open_ties:
                break
            li, row = open_ties[0]
            decided.add((li, row))
            self.tied += 1
            if moot(li, row):
                continue
            trial = {k: v.copy() for k, v in flips.items()}
            trial.setdefault(li, np.zeros((tp,), bool))[row] = True
            # the layers before ``li`` are the same under both picks, and
            # the layers past the judging one are run only for the pick
            # taken
            stop = self.m.n_layers if judge(li) is None else judge(li) + 1
            other = run(now["inputs"][li], li, trial, stop)
            for key in lists:
                other[key] = now[key][:li] + other[key]
            if far(other, li, row) < far(now, li, row):
                self.took_second += 1
                if stop < self.m.n_layers:
                    rest = run(other["h"], stop, trial)
                    for key in lists:
                        rest[key] = other[key] + rest[key]
                    other = rest
                flips, now = trial, other
        out = (now["gap"], now["best"])
        if kv is not None:
            out += (max(now["gaps"]),)
        if keep_kv:
            pairs = [tuple(np.asarray(z)[:rows].transpose(1, 0, 2)
                           for z in mine)
                     for mine in now["kept"] if mine is not None]
            out += ((np.concatenate([p[0] for p in pairs]),
                     np.concatenate([p[1] for p in pairs])),)
        return out

    def free(self) -> None:
        print(f"compared route_ties: {self.tied} token-layers within "
              f"{self.route_tie!r}, {self.took_second} went with the second "
              "pick", file=sys.stderr)
        self.embed = self.head = self.layers = self.held = None
