"""Qwen3-Next's block (``qwen3_next``) for the serving engine: Gated DeltaNet
layers that keep a recurrent state a slot, a grouped-query full-attention
layer every ``interval``-th, and in every layer softmax-routed experts, of
which the chip holds a share, beside a gated shared expert.

The third family ``serving/LMEngine`` serves. What stands where the engine
takes ``n_heads`` is a :class:`Qwen3NextBlock`, the static description the
jitted programs are keyed by. The tree carries the rest:

    {"embed": (V, D), "head": (V, D), "lnf": (D,),
     "layers": [{"ln1", "ln2",
                 # a Gated DeltaNet layer (l + 1 not a multiple of interval):
                 "w_qkvz" (D, 2 Hk dk + 2 H dv), "w_ba" (D, 2 H),
                 "conv" (K, 2 Hk dk + H dv), "A_log" (H,), "dt_bias" (H,),
                 "g_norm" (dv,), "w_out" (H dv, D),
                 # or a full-attention layer:
                 "wq" (D, Hq 2 hd), "wk" (D, Hkv hd), "wv" (D, Hkv hd),
                 "q_norm" (hd,), "k_norm" (hd,), "wo" (Hq hd, D),
                 # and in every layer the experts this chip holds:
                 "router" (D, E), "w_gate" (e, D, f), "w_up" (e, D, f),
                 "w_down" (e, f, D), "s_gate" (D, fs), "s_up" (D, fs),
                 "s_down" (fs, D), "shared_gate" (D,)}, ...]}

``w_qkvz``'s columns are q, k (Hk heads of dk), v, z (H heads of dv), in
that order; ``w_ba``'s are b, then a (H each); ``conv`` is the causal
depthwise convolution over q, k, v's ``2 Hk dk + H dv`` channels, tap j
applied to the input ``K - 1 - j`` tokens back. The norms ``ln1``, ``ln2``,
``lnf``, ``q_norm`` and ``k_norm`` are zero-centred (``x rsqrt(mean x^2 +
eps) (1 + w)``), the gated norm ``g_norm`` is not.

A layer's equations (x a row, m its norm):

* Gated DeltaNet: ``[q k v] = silu(conv([q k v]))``, q and k L2-normalised
  a head and q scaled by ``dk ** -0.5``, ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``, the gated delta rule
  (``ops/pallas/gated_delta``) from the slot's state, ``out = W_out
  (RMSNorm(o) g_norm silu(z))``.
* full attention: per head a query and a gate; q and k normed a head,
  rotary on their first ``rotary_dim`` dims (the halves are the pairs),
  ``group = Hq / Hkv`` query heads over a K/V head, ``o sigmoid(gate)``,
  ``W_o``.
* experts: ``p = softmax(m W_r)`` over all E, the ``top_k`` largest
  renormalised over themselves, ``y = sum w_e SwiGLU_e(m)`` over the picks
  of the experts held here (``held_from`` on, as many as the tree holds) +
  ``sigmoid(m . shared_gate) SwiGLU_shared(m)``. A pick of an expert held
  elsewhere adds nothing here.

One step (``decode_step_slots``) is ``glm_moe_lite.decode_step_slots``'s
counterpart: S decode rows at their own positions and, with a prompt lane,
P prompt rows of one slot, through ONE pass over the weights. A slot's
state is four stores (``store_shapes``): the full layers' K and V by K/V
head, each layer's recurrent state and the last ``K - 1`` convolution
inputs. A window that starts a prompt starts from zero state, so a slot's
state is replaced, inside the program, when a new request's first window
runs; a slot that decodes nothing leaves its state as it is. Besides the
logits and the stores a step returns its counts: the held experts that got
a row, the picks of held experts, all picks, and the slot-layers whose
recurrent state it updated.

float32 throughout, every product at ``highest``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas import decode_attention as grouped_attention
from ..ops.pallas.decode_attention import (decode_attention,
                                           lane_window_attention)
from ..ops.pallas.gated_delta import gated_delta_step, lane_window
from ..ops.pallas.moe_gemm import expert_gemm, pick_weights
from .glm_moe_lite import _rms, _silu, rotary

_PRECISION = "float32"

#: what the engine names this family in a refusal
DESCRIBED = "a Gated DeltaNet / grouped-attention / expert tree " \
    "(models/qwen3_next.py)"
#: the engine's stats a step's counts add to, in their order
COUNTS = ("experts_hit", "held_picks", "picks", "state_rows")
#: the kernel the full layers' rows are read by (its ``read_grain``)
ATTENTION = grouped_attention


@dataclass(frozen=True)
class Qwen3NextBlock:
    """What a program of this family is keyed by beside its shapes."""
    n_heads: int            # full attention's query heads
    n_kv_heads: int
    head_dim: int
    rotary_dim: int         # of a full-attention head's dims, the first
    lin_key_heads: int
    lin_value_heads: int
    lin_key_dim: int
    lin_value_dim: int
    interval: int           # full_attention_interval
    top_k: int              # experts a token
    held_from: int = 0      # the first expert this chip holds
    rope_theta: float = 1e7
    eps: float = 1e-6

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.interval == 0

    def index(self, layer: int) -> int:
        """The layer's place among the layers of its kind."""
        if self.is_full(layer):
            return layer // self.interval
        return layer - (layer + 1) // self.interval


def store_shapes(params: Dict[str, Any], n_slots: int, max_len: int,
                 block: Qwen3NextBlock):
    """The engine's stores for this tree, ``((K, V), (state, conv))``: K and
    V of the full layers by K/V head, ``(S, L_full, Hkv, max_len, hd)``
    each; the recurrent state, ``(S, L_lin, H, dk, dv)``; the convolution's
    last inputs, a store a linear layer, ``(S, K - 1, channels)`` each (a
    layer's store is replaced whole a step, which one store of all layers
    would rewrite in full for each)."""
    layers = params["layers"]
    full = sum(block.is_full(li) for li in range(len(layers)))
    lin = len(layers) - full
    taps, channels = next(layer["conv"].shape for layer in layers
                          if "conv" in layer)
    kv = (n_slots, full, block.n_kv_heads, max_len, block.head_dim)
    return ((kv, kv),
            ((n_slots, lin, block.lin_value_heads, block.lin_key_dim,
              block.lin_value_dim), ((n_slots, taps - 1, channels),) * lin))


def _norm(x, w, eps):
    """The zero-centred RMSNorm: the scale is ``1 + w``."""
    return _rms(x, 1.0 + w, eps)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _conv(win, w):
    """The convolution's output at the last row of each window (.., K, C)
    of the K inputs ending at it."""
    return jnp.sum(win * w, axis=-2)


def _linear(a, layer, block: Qwen3NextBlock, ii, s_rows, state, conv,
            active, lane):
    """A Gated DeltaNet layer's mixer over rows a (R, D): its output, the
    recurrent state's store and the layer's convolution store."""
    r = a.shape[0]
    hk, h = block.lin_key_heads, block.lin_value_heads
    dk, dv = block.lin_key_dim, block.lin_value_dim
    qkvz = jnp.dot(a, layer["w_qkvz"])
    ba = jnp.dot(a, layer["w_ba"])
    width = 2 * hk * dk + h * dv
    mixed, z = qkvz[:, :width], qkvz[:, width:].reshape(r, h, dv)
    w = layer["conv"]                                       # (K, C)
    win = jnp.concatenate([conv, mixed[:s_rows, None]], axis=1)
    out = _conv(win, w)
    new = win[:, 1:] if active is None else jnp.where(
        active[:, None, None], win[:, 1:], conv)
    if lane is not None:
        _, lslot, lpos0, lcnt = lane
        taps, p = w.shape[0], r - s_rows
        first = jax.lax.dynamic_index_in_dim(conv, lslot, 0, keepdims=False)
        ext = jnp.concatenate(
            [jnp.where(lpos0 == 0, 0.0, first), mixed[s_rows:]])
        out = jnp.concatenate([out, _conv(jnp.stack(
            [ext[j:j + p] for j in range(taps)], axis=1), w)])
        new = jax.lax.dynamic_update_index_in_dim(
            new, jax.lax.dynamic_slice_in_dim(ext, lcnt, taps - 1), lslot, 0)
    conv = new
    out = _silu(out)
    q = _l2(out[:, :hk * dk].reshape(r, hk, dk)) * dk ** -0.5
    k = _l2(out[:, hk * dk:2 * hk * dk].reshape(r, hk, dk))
    v = out[:, 2 * hk * dk:].reshape(r, h, dv)
    beta = jax.nn.sigmoid(ba[:, :h])
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(ba[:, h:]
                                                   + layer["dt_bias"])
    with jax.named_scope("gdn.decode"):
        o, state = gated_delta_step(
            q[:s_rows], k[:s_rows], v[:s_rows], jnp.exp(g[:s_rows]),
            beta[:s_rows], state, ii, active)
    if lane is not None:
        with jax.named_scope("gdn.lane"):
            ol, state = lane_window(q[s_rows:], k[s_rows:], v[s_rows:],
                                    g[s_rows:], beta[s_rows:], state, ii,
                                    lslot, lpos0, lcnt)
        o = jnp.concatenate([o, ol])
    o = _rms(o, layer["g_norm"], block.eps) * _silu(z)
    return jnp.dot(o.reshape(r, h * dv), layer["w_out"]), state, conv


def _full(a, layer, block: Qwen3NextBlock, fi, s_rows, positions, kc, vc,
          pos, active, lane):
    """A full-attention layer's mixer over rows a (R, D): its output and the
    K and V stores."""
    r = a.shape[0]
    hq, hkv, hd, rd = (block.n_heads, block.n_kv_heads, block.head_dim,
                       block.rotary_dim)
    qg = jnp.dot(a, layer["wq"]).reshape(r, hq, 2 * hd)
    q = _norm(qg[..., :hd], layer["q_norm"], block.eps)
    k = _norm(jnp.dot(a, layer["wk"]).reshape(r, hkv, hd), layer["k_norm"],
              block.eps)
    v = jnp.dot(a, layer["wv"]).reshape(r, hkv, hd)
    q, k = (jnp.concatenate([rotary(x[..., :rd], positions,
                                    block.rope_theta), x[..., rd:]], -1)
            for x in (q, k))
    q, k, v = q[:, :, None], k[:, :, None], v[:, :, None]
    with jax.named_scope("attn.grouped"):
        o, kc, vc = decode_attention(q[:s_rows], k[:s_rows], v[:s_rows], kc,
                                     vc, fi, pos, active, layer_axis=1)
        if lane is not None:
            _, lslot, lpos0, _ = lane
            ol, kc, vc = lane_window_attention(
                q[s_rows:], k[s_rows:], v[s_rows:], kc, vc, fi, lslot,
                lpos0)
            o = jnp.concatenate([o, ol])
    o = o[:, :, 0] * jax.nn.sigmoid(qg[..., hd:])
    return jnp.dot(o.reshape(r, hq * hd), layer["wo"]), kc, vc


def route(m, layer, block: Qwen3NextBlock, live=None):
    """The router's picks for rows m (R, D) among the experts held here:
    ``(weight, member)`` (R, e) each. Scores are ``softmax(m W_r)`` over all
    E experts; a row picks the ``top_k`` largest, weighed by their scores
    over the picks' sum; a pick of an expert held elsewhere is not in the
    result. Rows that are not ``live`` pick nothing."""
    with jax.named_scope("moe.route"):
        p = jax.nn.softmax(jnp.dot(m, layer["router"]), axis=-1)
        topv, topi = jax.lax.top_k(p, block.top_k)
        topw = topv / jnp.sum(topv, -1, keepdims=True)
        return pick_weights(topi - block.held_from, topw, live,
                            layer["w_gate"].shape[0])


def _moe(m, layer, block: Qwen3NextBlock, live):
    """The layer's experts over rows m, and its counts (held experts hit,
    held picks, all picks)."""
    weight, member = route(m, layer, block, live)
    with jax.named_scope("moe.expert_gemm"):
        y = expert_gemm(m, weight, member, layer["w_gate"], layer["w_up"],
                        layer["w_down"], k=block.top_k)
    with jax.named_scope("moe.shared"):
        y = y + jax.nn.sigmoid(jnp.dot(m, layer["shared_gate"]))[:, None] \
            * jnp.dot(_silu(jnp.dot(m, layer["s_gate"]))
                      * jnp.dot(m, layer["s_up"]), layer["s_down"])
    rows = m.shape[0] if live is None else jnp.sum(live)
    return y, jnp.stack([member.any(axis=0).sum(), member.sum(),
                         rows * block.top_k]).astype(jnp.int32)


def _window(params, tokens, kv, states, pos, active, block, lane):
    (kc, vc), (state, conv) = kv, states
    conv = list(conv)
    s_rows = tokens.shape[0]
    max_len = kc.shape[3]
    x = params["embed"][tokens]
    positions = pos
    live = active
    if lane is not None:
        ltok, _, lpos0, lcnt = lane
        p_rows = ltok.shape[0]
        x = jnp.concatenate([x, params["embed"][ltok]])
        positions = jnp.concatenate(
            [pos, lpos0 + jnp.arange(p_rows, dtype=pos.dtype)])
        alive = jnp.ones((s_rows,), bool) if active is None else active
        live = jnp.concatenate([alive, jnp.arange(p_rows) < lcnt])
    counts = jnp.zeros((3,), jnp.int32)
    n_lin = 0
    for li, layer in enumerate(params["layers"]):
        a = _norm(x, layer["ln1"], block.eps)
        if block.is_full(li):
            o, kc, vc = _full(a, layer, block, block.index(li), s_rows,
                              positions, kc, vc, pos, active, lane)
        else:
            ii = block.index(li)
            o, state, conv[ii] = _linear(a, layer, block, ii, s_rows, state,
                                         conv[ii], active, lane)
            n_lin += 1
        x = x + o
        y, hit = _moe(_norm(x, layer["ln2"], block.eps), layer, block, live)
        x = x + y
        counts = counts + hit
    decoding = s_rows if active is None else jnp.sum(active)
    state_rows = (decoding + (lane is not None)) * n_lin
    counts = jnp.concatenate([counts, jnp.reshape(state_rows, (1,))
                              .astype(jnp.int32)])
    # a row past capacity is poisoned, as causal_lm._lm_window poisons it
    over = (pos + 1 > max_len)[:, None]
    if lane is not None:
        x = jnp.concatenate(
            [x[:s_rows],
             jax.lax.dynamic_index_in_dim(x, s_rows + lcnt - 1, 0)])
        over = jnp.concatenate([over, jnp.zeros((1, 1), bool)])
    x = jnp.where(over, jnp.nan, _norm(x, params["lnf"], block.eps))
    logits = jnp.dot(x, params["head"].T)
    return logits, (kc, vc), (state, tuple(conv)), counts


def decode_step_slots(params: Dict[str, Any], tokens: jax.Array, kv,
                      states, poss: jax.Array, block: Qwen3NextBlock,
                      active=None, lane=None) -> Tuple[Any, ...]:
    """One decode step for S independent streams at per-slot positions,
    ``causal_lm.lm_decode_step_slots``'s counterpart for this family.

    tokens: (S, 1, 1) int32; kv, states: the stores (``store_shapes``);
    poss: (S, 1); active: (S,) bool or None; ``lane``: ``(tokens (P,),
    slot, pos0, count)``, a window of P prompt rows of one slot that is not
    active. Returns (logits (S, 1, V), or (S + 1, 1, V) with a lane, whose
    last row is the lane's row ``count - 1``; kv; states; poss + 1; counts
    (4,) int32 in the order of ``COUNTS``)."""
    with jax.default_matmul_precision(_PRECISION):
        logits, kv, states, counts = _window(
            params, tokens[:, 0, 0], kv, states, poss[:, 0], active, block,
            lane)
    return logits[:, None], kv, states, poss + 1, counts
