"""GLM-4.7-Flash's block (``glm4_moe_lite``) for the serving engine: latent
attention over one stored row a token a layer, a leading dense layer, and
layers of sigmoid-routed experts with a shared one.

The second family ``serving/LMEngine`` serves. A tree of this family is
told from the GPT-2 tree (``models/causal_lm.py``) by what stands where
the engine takes ``n_heads``: there an int, here a :class:`GlmBlock`, the
static description the jitted programs are keyed by (everything a program
needs that no shape says: the heads' split, the experts a token, the
router's scale, the rotary base). The tree carries the rest:

    {"embed": (V, D), "head": (V, D), "lnf": (D,),
     "layers": [{"ln1", "wq_a" (D, Q), "q_norm" (Q,), "wq_b" (Q, H*(n+r)),
                 "wkv_a" (D, C+r), "kv_norm" (C,), "wk_b" (H, n, C),
                 "wv_b" (H, C, v), "wo" (H*v, D), "ln2",
                 # a dense layer:
                 "w_gate" (D, F), "w_up" (D, F), "w_down" (F, D)
                 # or an expert layer:
                 "router" (D, E), "route_bias" (E,),
                 "w_gate" (E, D, f), "w_up" (E, D, f), "w_down" (E, f, D),
                 "s_gate" (D, f), "s_up" (D, f), "s_down" (f, D)}, ...]}

A layer is a dict of its own and not a slice of a stack: a kernel's operand
that is a slice of a stacked 2.4 GB tensor would be copied every step.
``wk_b`` and ``wv_b`` are the published ``kv_b_proj`` (C, H*(n+v)) split by
what the absorbed form contracts (``split_kv_b``).

One step (``decode_step_slots``) is ``causal_lm.lm_decode_step_slots``'s
counterpart: S decode rows at their own positions and, with a prompt lane,
P prompt rows of one slot under them, through ONE pass over the weights.
Attention is the absorbed form throughout (``ops/pallas/latent_attention``:
the kernel for the decode rows, the dense prefix form for the lane's
window); the routed experts are ``ops/pallas/moe_gemm``'s dropless grouped
GEMM. Rows that hold nothing (a slot without a request, a window's padding)
pick no expert. Besides the logits and the stores a step returns its
routing's counts.

float32 throughout, every product at ``highest``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.latent_attention import (LANES, latent_decode_attention,
                                           latent_lane_attention)
from ..ops.pallas.moe_gemm import expert_gemm, pick_weights

_PRECISION = "float32"


@dataclass(frozen=True)
class GlmBlock:
    """What a program of this family is keyed by beside its shapes."""
    n_heads: int
    qk_nope: int            # a head's query/key dims without position
    qk_rope: int            # and with rotary position
    v_head: int
    top_k: int              # experts a token
    route_scale: float      # routed_scaling_factor
    rope_theta: float = 1e6
    eps: float = 1e-5

    @property
    def sm_scale(self) -> float:
        return float(self.qk_nope + self.qk_rope) ** -0.5


def split_kv_b(kv_b, block: GlmBlock):
    """The published ``kv_b_proj`` (C, H*(n+v)) as the absorbed form's two
    factors: ``wk_b`` (H, n, C) and ``wv_b`` (H, C, v)."""
    c = kv_b.shape[0]
    w = kv_b.reshape(c, block.n_heads, block.qk_nope + block.v_head)
    return (w[:, :, :block.qk_nope].transpose(1, 2, 0),
            w[:, :, block.qk_nope:].transpose(1, 0, 2))


def store_shapes(params: Dict[str, Any], n_slots: int, max_len: int
                 ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The engine's two stores for this tree: the latent as planes of 128
    lanes, ``(S, L * C/128, max_len, 128)``, and the rotary key padded to
    the lanes, ``(S, L, max_len, 128)``; a slot's rows at ``[slot, :,
    :rows]``."""
    layers = params["layers"]
    c = layers[0]["kv_norm"].shape[0]
    if c % LANES:
        raise ValueError(f"latent width {c} is no multiple of {LANES}")
    return ((n_slots, len(layers) * (c // LANES), max_len, LANES),
            (n_slots, len(layers), max_len, LANES))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rotary(x, pos, theta: float):
    """Rotary position on the last axis of x (rows, .., r), rows at
    ``pos`` (rows,): the two halves of the axis are the pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(m, layer, block: GlmBlock, live=None):
    """The router's picks for rows m (R, D): ``(weight, member)`` (R, E)
    each. Scores are ``sigmoid(m W_r)``; a row picks the ``top_k`` largest
    of score + ``route_bias``; its weights are the picked scores WITHOUT
    the bias, over their sum, times ``route_scale``. Rows that are not
    ``live`` pick nothing."""
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(m, layer["router"]))
        _, topi = jax.lax.top_k(s + layer["route_bias"], block.top_k)
        tops = jnp.take_along_axis(s, topi, axis=-1)
        topw = tops / jnp.sum(tops, -1, keepdims=True) * block.route_scale
        return pick_weights(topi, topw, live, s.shape[-1])


def _mlp(m, layer, block: GlmBlock, live):
    """The layer's MLP over rows m, and its routing's counts (experts hit,
    picks) or None for a dense layer."""
    if "router" not in layer:
        return jnp.dot(_silu(jnp.dot(m, layer["w_gate"]))
                       * jnp.dot(m, layer["w_up"]), layer["w_down"]), None
    weight, member = route(m, layer, block, live)
    with jax.named_scope("moe.expert_gemm"):
        y = expert_gemm(m, weight, member, layer["w_gate"], layer["w_up"],
                        layer["w_down"], k=block.top_k)
    with jax.named_scope("moe.shared"):
        y = y + jnp.dot(_silu(jnp.dot(m, layer["s_gate"]))
                        * jnp.dot(m, layer["s_up"]), layer["s_down"])
    counts = jnp.stack([member.any(axis=0).sum(), member.sum()])
    return y, counts.astype(jnp.int32)


def _window(params, tokens, cc, rc, pos, active, block: GlmBlock, lane):
    s_rows = tokens.shape[0]
    n_layers = rc.shape[1]
    max_len = rc.shape[2]
    x = params["embed"][tokens]
    positions = pos
    live = active
    if lane is not None:
        ltok, lslot, lpos0, lcnt = lane
        p_rows = ltok.shape[0]
        x = jnp.concatenate([x, params["embed"][ltok]])
        positions = jnp.concatenate(
            [pos, lpos0 + jnp.arange(p_rows, dtype=pos.dtype)])
        alive = jnp.ones((s_rows,), bool) if active is None else active
        live = jnp.concatenate([alive, jnp.arange(p_rows) < lcnt])
    cc = cc.reshape(cc.shape[0], n_layers, -1, max_len, LANES)
    h, nope, eps = block.n_heads, block.qk_nope, block.eps
    counts = jnp.zeros((2,), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        a = _rms(x, layer["ln1"], eps)
        q = jnp.dot(_rms(jnp.dot(a, layer["wq_a"]), layer["q_norm"], eps),
                    layer["wq_b"]).reshape(-1, h, nope + block.qk_rope)
        qr = rotary(q[..., nope:], positions, block.rope_theta)
        qa = jnp.einsum("rhn,hnc->rhc", q[..., :nope], layer["wk_b"])
        kv = jnp.dot(a, layer["wkv_a"])
        width = layer["kv_norm"].shape[0]
        c = _rms(kv[:, :width], layer["kv_norm"], eps)
        kr = rotary(kv[:, width:], positions, block.rope_theta)
        with jax.named_scope("mla.decode_attention"):
            o, cc, rc = latent_decode_attention(
                qa[:s_rows], qr[:s_rows], c[:s_rows], kr[:s_rows], cc, rc,
                li, pos, active, sm_scale=block.sm_scale)
        if lane is not None:
            with jax.named_scope("mla.lane_attention"):
                ol, cc, rc = latent_lane_attention(
                    qa[s_rows:], qr[s_rows:], c[s_rows:], kr[s_rows:], cc,
                    rc, li, lslot, lpos0, sm_scale=block.sm_scale)
            o = jnp.concatenate([o, ol])
        o = jnp.einsum("rhc,hcv->rhv", o, layer["wv_b"])
        x = x + jnp.dot(o.reshape(o.shape[0], -1), layer["wo"])
        y, hit = _mlp(_rms(x, layer["ln2"], eps), layer, block, live)
        x = x + y
        if hit is not None:
            counts = counts + hit
    # a row past capacity is poisoned, as causal_lm._lm_window poisons it
    over = (pos + 1 > max_len)[:, None]
    if lane is not None:
        x = jnp.concatenate(
            [x[:s_rows],
             jax.lax.dynamic_index_in_dim(x, s_rows + lcnt - 1, 0)])
        over = jnp.concatenate([over, jnp.zeros((1, 1), bool)])
    x = jnp.where(over, jnp.nan, _rms(x, params["lnf"], eps))
    logits = jnp.dot(x, params["head"].T)
    return logits, cc.reshape(cc.shape[0], -1, max_len, LANES), rc, counts


def decode_step_slots(params: Dict[str, Any], tokens: jax.Array,
                      cc: jax.Array, rc: jax.Array, poss: jax.Array,
                      block: GlmBlock, active=None, lane=None):
    """One decode step for S independent streams at per-slot positions,
    ``causal_lm.lm_decode_step_slots``'s counterpart for this family.

    tokens: (S, 1, 1) int32; cc, rc: the stores (``store_shapes``); poss:
    (S, 1); active: (S,) bool or None; ``lane``: ``(tokens (P,), slot, pos0,
    count)``, a window of P prompt rows of one slot that is not active.
    Returns (logits (S, 1, V), or (S + 1, 1, V) with a lane, whose last row
    is the lane's row ``count - 1``; cc; rc; poss + 1; counts (2,) int32:
    over the step's expert layers, the distinct experts that got a row,
    and the picks made, rows x ``top_k``)."""
    with jax.default_matmul_precision(_PRECISION):
        logits, cc, rc, counts = _window(
            params, tokens[:, 0, 0], cc, rc, poss[:, 0], active, block, lane)
    return logits[:, None], cc, rc, poss + 1, counts
