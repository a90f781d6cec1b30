"""Causal transformer LM with a streaming KV-cache decode step.

Long-context streaming as a *pipeline loop* (the tensor_repo recurrence
the reference uses for its LSTM example, tests/nnstreamer_repo_lstm):
the KV cache is carried as ordinary device-resident stream tensors, so
autoregressive decoding is

    tokens ─┐
            ├─ tensor_mux ! tensor_filter(zoo://causal_lm?...) ! demux
    state ──┘        ▲                                        │
  (reposrc)          └── logits → sink;  (k,v,pos) → reposink ┘

One token per loop iteration, O(1) work per step against an O(max_len)
cache — no recompute of the prefix. Shapes are static (cache is
pre-allocated at ``max_len``; ``pos`` masks the unwritten tail) so XLA
compiles the step exactly once.

Exactness contract: step-decoding a sequence token-by-token produces the
same logits as the full causal forward pass (``lm_forward``) at every
position (tests/test_causal_lm.py).

Cache transport layout: rank-3 ``(layers·batch·heads, max_len, head_dim)``
so it rides the tensor type system's rank limit; the step reshapes to the
logical 5-D layout internally.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import TensorsInfo
from ..ops.int8 import matmul_any as _mm
from ..ops.int8 import mlp_matmul as _mlp
from ..ops.int8 import quantize_weight, stack_shape
from ..ops.pallas.decode_attention import (decode_attention,
                                           lane_window_attention,
                                           window_attention_reference)
from . import glm_moe_lite, qwen3_next
from .zoo import ModelBundle, register_model


def init_causal_lm(rng: jax.Array, vocab: int, d_model: int, n_heads: int,
                   n_layers: int, max_len: int,
                   d_ff: int = 0) -> Dict[str, jax.Array]:
    d_ff = d_ff or 4 * d_model
    ks = jax.random.split(rng, 6)
    s = 1.0 / math.sqrt(d_model)
    sf = 1.0 / math.sqrt(d_ff)
    L = n_layers
    return {
        "embed": jax.random.normal(ks[0], (vocab, d_model)) * 0.02,
        "pos_embed": jax.random.normal(ks[1], (max_len, d_model)) * 0.02,
        "wqkv": jax.random.normal(ks[2], (L, d_model, 3 * d_model)) * s,
        "wo": jax.random.normal(ks[3], (L, d_model, d_model)) * s,
        "w1": jax.random.normal(ks[4], (L, d_model, d_ff)) * s,
        "w2": jax.random.normal(ks[5], (L, d_ff, d_model)) * sf,
        "ln1": jnp.ones((L, d_model)),
        "ln2": jnp.ones((L, d_model)),
        "lnf": jnp.ones((d_model,)),
    }


def quantize_lm_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """w8a8 serving form of an LM param tree: the four GEMM stacks
    (wqkv/wo/w1/w2) become int8 payloads + per-output-channel scales
    (ops/int8.quantize_weight); embeddings and norms stay float. Every
    execution form — forward, prefill (dense/flash/ring), decode step,
    verify window, batched slots — consumes the quantized tree through
    the same ``matmul_any`` sites, so this one transform turns the whole
    family int8 with no flag-threading; the scanned layer stacks slice
    into per-layer quantized dicts transparently. TPU v5e runs the int8
    contractions at 2x the bf16 peak (docs/performance.md roofline).
    Composes with the TP mesh: `parallel/tp_decode.tp_shard_params`
    relayouts a quantized tree preserving the single-device grids, so
    distributed int8 decode matches this path token-for-token."""
    if "layers" in params:
        raise ValueError(
            "quantize_lm_params: the w8a8 form exists for the GPT-2 tree's "
            "four GEMM stacks only; a latent-attention / expert tree "
            "(models/glm_moe_lite.py) is served in float32")
    qp = dict(params)
    for k in ("wqkv", "wo", "w1", "w2"):
        qp[k] = quantize_weight(params[k])
    return qp


def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _split_heads(t, n_heads):
    b, l, d = t.shape
    return t.reshape(b, l, n_heads, d // n_heads).transpose(0, 2, 1, 3)


#: TPU matmuls default to bf16 accumulation, which makes prefill vs
#: step-decode logits drift ~1e-3 (different contraction orders). This
#: family's contract is exactness between its execution forms, so its
#: matmuls pin float32 precision (measured 6e-8 agreement on v5e).
#: Large production models would keep bf16 and accept the drift.
_PRECISION = "float32"


def lm_forward(params: Dict[str, jax.Array], tokens: jax.Array,
               n_heads: int) -> jax.Array:
    """Full causal forward (the oracle): (B, T) int32 → (B, T, vocab)."""
    with jax.default_matmul_precision(_PRECISION):
        return _lm_forward(params, tokens, n_heads)


def _block_body(h, layer, mask, n_heads, attention_fn=None):
    """One transformer block over a full (masked) sequence; returns the
    new hidden state plus this layer's per-head K/V (for cache prefill).
    The ONE definition all full-sequence execution forms share.
    ``attention_fn`` (q,k,v)->o replaces the dense causal attention
    (e.g. sequence-parallel ring attention — it must apply causality
    itself)."""
    wqkv, wo, w1, w2, ln1, ln2 = layer
    a = _ln(h, ln1)
    q, k, v = jnp.split(_mm(a, wqkv), 3, axis=-1)
    qh, kh, vh = (_split_heads(z, n_heads) for z in (q, k, v))
    if attention_fn is not None:
        o = attention_fn(qh, kh, vh)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(qh.shape[-1])
        s = jnp.where(mask, s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vh)
    o = o.transpose(0, 2, 1, 3).reshape(h.shape)
    h = h + _mm(o, wo)
    m = _ln(h, ln2)
    return h + _mlp(m, w1, w2), kh, vh


def _layer_stack(params):
    return (params["wqkv"], params["wo"], params["w1"], params["w2"],
            params["ln1"], params["ln2"])


def _lm_forward(params, tokens, n_heads):
    b, t = tokens.shape
    x = params["embed"][tokens] + params["pos_embed"][:t][None]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def block(h, layer):
        h, _, _ = _block_body(h, layer, mask, n_heads)
        return h, None

    x, _ = jax.lax.scan(block, x, _layer_stack(params))
    return _ln(x, params["lnf"]) @ params["embed"].T


def lm_prefill(params: Dict[str, jax.Array], tokens: jax.Array,
               n_heads: int, max_len: int, mesh=None,
               sp_axis: str = "sp", flash: "bool | None" = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Process a whole prompt in ONE forward and emit the populated cache.

    tokens: (B, T) int32 with T <= max_len. Returns (logits_last (B, vocab),
    kcache, vcache, pos=T) in the flat transport layout — decode then
    continues token-by-token via ``lm_decode_step``. This is the standard
    prefill/decode split: prompt cost is one big (MXU-friendly) forward,
    not T sequential steps.

    With ``mesh``, prompt attention runs **sequence-parallel** over
    ``mesh[sp_axis]`` via causal ring attention (parallel/ring.py):
    prompt length scales with the axis size (T must divide by it) while
    the emitted cache and subsequent decode are unchanged — long-context
    prefill across chips, streaming decode after.

    ``flash=True`` (single-device) swaps the dense attention for the
    blockwise pallas kernel — no (T, T) score matrix in HBM. Defaults to
    the ``NNS_LM_FLASH=1`` env var; either way the choice resolves at
    TRACE time and is baked into a jitted prefill's cached executable.
    """
    with jax.default_matmul_precision(_PRECISION):
        return _lm_prefill(params, tokens, n_heads, max_len, mesh, sp_axis,
                           flash)


def _lm_prefill(params, tokens, n_heads, max_len, mesh=None, sp_axis="sp",
                flash=None, true_len=None):
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(
            f"lm_prefill: prompt length {t} exceeds max_len={max_len}")
    if true_len is not None and (mesh is not None or flash):
        raise ValueError(
            "lm_prefill: true_len= (padded-prompt masking) is a "
            "dense-attention feature; the ring/flash paths apply "
            "causality internally and cannot see it")
    if true_len is not None and not isinstance(true_len, jax.core.Tracer):
        # eager mirror of tp_prefill's check — only when the value is
        # concrete (under jit it is a tracer and the caller's eager
        # entry point has already validated it)
        tl_v = int(true_len)
        if not 1 <= tl_v <= t:
            raise ValueError(
                f"lm_prefill: true_len={tl_v} outside [1, {t}] "
                "(padded prompt length)")
    n_layers = stack_shape(params["wqkv"])[0]
    d_model = params["embed"].shape[1]
    hd = d_model // n_heads
    x = params["embed"][tokens] + params["pos_embed"][:t][None]
    pad = [(0, 0), (0, 0), (0, max_len - t), (0, 0)]
    attn = mask = None
    if mesh is not None:
        from ..parallel.ring import sp_attention_fn

        if sp_axis not in mesh.shape:
            raise ValueError(
                f"lm_prefill: mesh has no {sp_axis!r} axis "
                f"(axes: {dict(mesh.shape)})")
        if t % mesh.shape[sp_axis]:
            raise ValueError(
                f"lm_prefill: prompt length {t} not divisible by the "
                f"{sp_axis!r} axis size {mesh.shape[sp_axis]}")
        if flash:
            raise ValueError(
                "lm_prefill: flash=True conflicts with mesh= (the sp path "
                "uses ring attention; run flash single-device)")
        # NNS_LM_SP_MODE=ring-flash composes the pallas kernel inside the
        # ring steps (long-context memory profile); default plain ring
        attn = sp_attention_fn(os.environ.get("NNS_LM_SP_MODE", "ring"),
                               mesh, sp_axis, causal=True)
    elif true_len is None and (
            flash if flash is not None
            else os.environ.get("NNS_LM_FLASH", "") == "1"):
        # (true_len forces the dense branch even under NNS_LM_FLASH=1:
        # the kernel applies causality internally and cannot column-mask
        # a padded prompt — explicit flash=True raised above)
        # single-device flash path: blockwise pallas kernel, no (t, t)
        # score matrix in HBM (ops/pallas/flash_attention.py). NOTE: both
        # the explicit flag and the env var resolve at TRACE time — a
        # jitted prefill bakes the choice into the cached executable
        from ..ops.pallas.flash_attention import flash_attention

        attn = lambda qh, kh, vh: flash_attention(  # noqa: E731
            qh, kh, vh, causal=True)
    else:
        # only the dense path needs the O(t²) mask; the sp path exists
        # precisely to avoid materializing it on one device
        mask = jnp.tril(jnp.ones((t, t), bool))
        if true_len is not None:
            # right-padded prompt: padded columns can never be attended
            tl = jnp.asarray(true_len).reshape(()).astype(jnp.int32)
            mask = mask & (jnp.arange(t) < tl)[None, :]

    def block(h, layer):
        h, kh, vh = _block_body(h, layer, mask, n_heads, attn)
        return h, (jnp.pad(kh, pad), jnp.pad(vh, pad))

    x, (kc, vc) = jax.lax.scan(block, x, _layer_stack(params))
    if true_len is None:
        last = x[:, -1:]
        pos = jnp.full((1,), t, jnp.int32)
    else:
        last = jax.lax.dynamic_index_in_dim(x, tl - 1, axis=1,
                                            keepdims=True)
        pos = tl.reshape(1)
    logits = (_ln(last, params["lnf"]) @ params["embed"].T)[:, 0]
    flat = (n_layers * b * n_heads, max_len, hd)
    return logits, kc.reshape(flat), vc.reshape(flat), pos


def lm_decode_step(params: Dict[str, jax.Array], token: jax.Array,
                   kcache: jax.Array, vcache: jax.Array, pos: jax.Array,
                   n_heads: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One streaming decode step.

    token: (B, 1) int32; kcache/vcache: (L·B·H, max_len, hd) flat transport
    layout; pos: (1,) int32 — next write position. Returns
    (logits (B, vocab), kcache', vcache', pos+1).

    Cache-capacity contract: callers must stop at ``pos == max_len``
    (prompt + generated tokens ≤ the cache's max_len). Decoding past
    capacity cannot raise from inside the compiled program (pos is a
    traced value), so the step NaN-poisons the logits instead —
    ``dynamic_update_slice`` would otherwise clamp the write onto the
    last slot and return silently wrong results.
    """
    with jax.default_matmul_precision(_PRECISION):
        return _lm_decode_step(params, token, kcache, vcache, pos, n_heads)


def _lm_decode_step(params, token, kcache, vcache, pos, n_heads):
    # exactly the W=1 case of the verify window (one shared body — the
    # cache-write/masking/poison contracts live in one place)
    logits, kc, vc, pos = _lm_verify_window(
        params, token, kcache, vcache, pos, n_heads)
    return logits[:, 0], kc, vc, pos


def lm_verify_window(params: Dict[str, jax.Array], tokens: jax.Array,
                     kcache: jax.Array, vcache: jax.Array, pos: jax.Array,
                     n_heads: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Speculative-verify step: consume a WINDOW of W tokens at cache
    positions pos..pos+W-1 and return logits at EVERY window position.

    The device half of speculative decoding: the caller feeds
    ``[carried_token, draft_1..draft_{W-1}]``, gets back the model's
    next-token distribution after each of them in ONE dispatch, and
    accepts the longest prefix where the draft matches the model
    (`serving/lm_engine.py` speculative mode). Within the window, query
    row j attends cache columns <= pos+j — K/V for the whole window are
    written first, and rows never see later columns, so row j's logits
    equal a sequential decode step that consumed tokens[:, :j+1] up to
    matmul associativity (~1e-7 at f32: the W-row matmul contracts in a
    different order) with identical argmax except at ties below that
    scale — greedy acceptance reproduces sequential greedy decode
    (tests/test_lm_spec.py pins both levels).
    Rejected-draft K/V slots beyond the accepted count become garbage,
    but a later step at position p attends col <= p only after
    overwriting slot p — the same overwrite-before-visible invariant
    bucketed prefill relies on (lm_prefill_masked), so the caller
    "rolls back" by just setting pos lower.

    tokens: (B, W) int32; caches in the flat transport layout; pos:
    (1,) int32. Returns (logits (B, W, vocab), kcache', vcache',
    pos+W). Windows past capacity (pos+W > max_len) NaN-poison the
    logits, mirroring lm_decode_step's contract.
    """
    with jax.default_matmul_precision(_PRECISION):
        return _lm_verify_window(
            params, tokens, kcache, vcache, pos, n_heads)


def _lm_verify_window(params, tokens, kcache, vcache, pos, n_heads):
    """Streams in step: one position for the whole batch, the flat
    transport layout ``(L·B·H, max_len, hd)``."""
    n_layers = stack_shape(params["wqkv"])[0]
    b, w = tokens.shape
    max_len, hd = kcache.shape[-2:]
    p = jnp.asarray(pos).reshape(())
    shape5 = (n_layers, b, n_heads, max_len, hd)
    logits, kc, vc = _lm_window(
        params, tokens, kcache.reshape(shape5), vcache.reshape(shape5), p,
        None, n_heads, layer_axis=0)
    return (logits, kc.reshape(kcache.shape), vc.reshape(vcache.shape),
            (p + w).reshape(1).astype(jnp.int32))


def _lm_window(params, tokens, kc, vc, pos, active, n_heads, layer_axis,
               lane=None):
    """The ONE decode / verify body: a window of W tokens a stream, over
    the 5-D store with its layers on ``layer_axis`` (0: ``(L, B, H,
    max_len, hd)``, streams in step; 1: ``(B, L, H, max_len, hd)``, a store
    a slot). ``pos`` is () for streams in step or (B,) per stream;
    ``active`` (B,) bool or None marks the streams that hold a request.
    Returns (logits (B, W, vocab), kc, vc).

    A window of one token goes through ``ops/pallas.decode_attention``:
    on a TPU the kernel reads the rows ``< pos`` of the streams that are
    active and writes each new row in place; elsewhere, and for a wider
    window, the dense masked form runs (its per-stream write is a masked
    rewrite of the store, its read the whole ``max_len`` axis).

    ``lane`` (one-token windows over a store a slot only) adds one window
    of P prompt rows of ONE stream to the step: ``(tokens (P,), slot,
    pos0, count)``, the prompt's tokens ``pos0 .. pos0 + P - 1`` of which
    the first ``count`` are real. The P rows ride the B decode rows through
    the same norms and matmuls, so each weight is read once for both;
    their attention and their in-place K/V write are
    ``lane_window_attention``'s, and the stream they belong to has to be
    one that is not active. The logits then carry one row more, (B + 1, 1,
    vocab): the last is the lane's row ``count - 1``, the prompt's last
    token where the window is the prompt's last."""
    w = tokens.shape[1]
    b = tokens.shape[0]
    max_len = kc.shape[-2]
    if pos.ndim == 0:
        pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos, w)[None]
    else:
        pe = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], p, w))(pos)
    x = params["embed"][tokens] + pe
    if lane is not None:
        if w != 1 or layer_axis != 1:
            raise ValueError("a prompt lane rides one-token windows over "
                             "a store a slot")
        ltok, lslot, lpos0, lcnt = lane
        # the lane's rows as P more one-token windows under the decode rows
        x = jnp.concatenate(
            [x, (params["embed"][ltok] + jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], lpos0, ltok.shape[0]))[:, None]])

    def block(carry, layer):
        # the store rides the CARRY, not the scan ys: a ys-threaded store
        # makes XLA rewrite all L·B·H·max_len rows every token, while a
        # carried buffer is updated in place, by the kernel's one-row DMA
        # or the dense form's dynamic_update_slice
        h, kc, vc = carry
        wqkv, wo, w1, w2, ln1, ln2, li = layer
        a = _ln(h, ln1)
        q, k, v = (_split_heads(z, n_heads)                # (B, H, W, hd)
                   for z in jnp.split(_mm(a, wqkv), 3, axis=-1))
        if lane is not None:
            o, kc, vc = decode_attention(
                q[:b], k[:b], v[:b], kc, vc, li, pos, active,
                layer_axis=layer_axis)
            ol, kc, vc = lane_window_attention(
                q[b:], k[b:], v[b:], kc, vc, li, lslot, lpos0)
            o = jnp.concatenate([o, ol])
        elif w == 1:
            o, kc, vc = decode_attention(
                q, k, v, kc, vc, li, pos, active, layer_axis=layer_axis)
        else:
            o, kc, vc = window_attention_reference(
                q, k, v, kc, vc, li, pos, active, layer_axis=layer_axis)
        o = o.transpose(0, 2, 1, 3).reshape(h.shape)
        h = h + _mm(o, wo)
        m = _ln(h, ln2)
        return (h + _mlp(m, w1, w2), kc, vc), None

    (x, kc, vc), _ = jax.lax.scan(
        block, (x, kc, vc),
        (*_layer_stack(params),
         jnp.arange(kc.shape[layer_axis], dtype=jnp.int32)),
        # full unroll: step ops are tiny (B·W rows), so the win is XLA
        # prefetching the next layer's weights while this one runs;
        # n_layers is small and static, compile cost is bounded
        unroll=True)
    # cache overflow (window past capacity) surfaces as NaN logits, not
    # as a silent clamped overwrite of the last slots — lm_decode_step
    # doc. The hidden row is poisoned, (B, W, D), and the unembedding
    # carries the NaN to every logit of that stream
    over = (pos + w > max_len).reshape(-1, 1, 1)
    if lane is not None:
        # one row of the lane is unembedded, beside the decode rows: the
        # embedding is read once for both
        x = jnp.concatenate(
            [x[:b], jax.lax.dynamic_index_in_dim(x, b + lcnt - 1, 0)])
        over = jnp.concatenate([over, jnp.zeros((1, 1, 1), bool)])
    x = jnp.where(over, jnp.nan, _ln(x, params["lnf"]))
    return x @ params["embed"].T, kc, vc                 # (B, W, vocab)


def lm_verify_window_slots(params: Dict[str, jax.Array], tokens: jax.Array,
                           kcaches: jax.Array, vcaches: jax.Array,
                           poss: jax.Array, n_heads: int,
                           active: "jax.Array | None" = None, lane=None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array]:
    """Verify windows for S independent streams at per-slot positions:
    the S slots are the batch of the one shared body, each row with its
    own position. tokens: (S, W); caches with a leading slot axis,
    ``(S, L·H, max_len, hd)``; poss: (S, 1); active: (S,) bool, the slots
    that hold a request (None: all). A slot that is not active costs no
    K/V read on a TPU and writes nothing; its logits are not meaningful.
    ``lane`` (W = 1 only) is ``_lm_window``'s: a window of prompt rows of
    one inactive slot, and one more row of logits for it.
    Returns (logits (S, W, vocab), caches', poss+W)."""
    s, w = tokens.shape
    lh, max_len, hd = kcaches.shape[1:]
    shape5 = (s, lh // n_heads, n_heads, max_len, hd)
    with jax.default_matmul_precision(_PRECISION):
        logits, kc, vc = _lm_window(
            params, tokens, kcaches.reshape(shape5),
            vcaches.reshape(shape5), poss[:, 0], active, n_heads,
            layer_axis=1, lane=lane)
    return (logits, kc.reshape(kcaches.shape), vc.reshape(vcaches.shape),
            poss + w)


def lm_prefill_masked(params: Dict[str, jax.Array], tokens: jax.Array,
                      true_len: jax.Array, n_heads: int, max_len: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Prefill a right-PADDED prompt exactly: ``tokens`` is (1, Tb) with
    the real prompt in the first ``true_len`` positions (traced scalar).

    Serving engines bucket prompt lengths (pad Tb up to a few fixed
    sizes) so admission costs one compile per BUCKET, not per distinct
    prompt length. Exactness relies on two masks: attention columns are
    limited to ``col < true_len`` (padded rows can't leak in), and the
    returned last-token logits come from row ``true_len - 1``. K/V
    written at positions >= true_len ARE garbage, but a decode step at
    position p attends only ``col <= p`` after overwriting slot p, so a
    garbage slot is always overwritten before it becomes visible
    (`serving/lm_engine.py` relies on this).

    Returns (logits (1, vocab), kcache, vcache, pos=true_len) in the
    same flat transport layout as ``lm_prefill`` — it IS ``_lm_prefill``
    (one shared body) with the extra column mask and last-row selection.
    """
    with jax.default_matmul_precision(_PRECISION):
        return _lm_prefill(params, tokens, n_heads, max_len,
                           true_len=true_len)


def lm_decode_step_slots(params: Dict[str, jax.Array], tokens: jax.Array,
                         kcaches: jax.Array, vcaches: jax.Array,
                         poss: jax.Array, n_heads: int,
                         active: "jax.Array | None" = None, lane=None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """One decode step for S INDEPENDENT streams at per-slot positions.

    The continuous-batching primitive: the S slots run as the batch of
    the single-stream body, each with its own cache, write position and
    liveness, while the matmuls batch onto the MXU. On a TPU a slot's
    attention reads the rows it holds and its new row is written in
    place (``ops/pallas.decode_attention``). Exactness with the
    single-stream path is by construction (one body;
    tests/test_lm_serving.py pins it).

    tokens: (S, 1, 1) int32; kcaches/vcaches: (S, layers·heads, max_len,
    head_dim); poss: (S, 1) int32; active: (S,) bool or None. Returns
    (logits (S, 1, vocab), kcaches', vcaches', poss+1). Slots past
    capacity NaN-poison their own row only. Exactly the W=1 case of
    :func:`lm_verify_window_slots`.

    ``lane``: ``(tokens (P,), slot, pos0, count)``, a window of P prompt
    rows of one slot that is not active, prefilled inside this step (one
    pass over the weights for both; ``_lm_window``). Its K/V rows land in
    the slot's store at ``pos0 .. pos0 + P - 1`` and the logits get one
    more row, (S + 1, 1, vocab): the lane's row ``count - 1``.
    """
    return lm_verify_window_slots(
        params, tokens[:, :, 0], kcaches, vcaches, poss, n_heads, active,
        lane)


#: the families served beside the GPT-2 block, by the type of the static
#: description that stands where that block takes ``n_heads``
_FAMILIES = {glm_moe_lite.GlmBlock: glm_moe_lite,
             qwen3_next.Qwen3NextBlock: qwen3_next}


def family(n_heads):
    """The module of the family whose static description stands for
    ``n_heads`` (``glm_moe_lite`` for a ``GlmBlock``, ``qwen3_next`` for a
    ``Qwen3NextBlock``), or None for the GPT-2 block's head count. A family
    module has ``store_shapes``, ``decode_step_slots``, ``COUNTS`` (the
    engine stats its step's counts add to), ``ATTENTION`` (the kernel
    module whose ``read_grain`` its stored rows are read at) and
    ``DESCRIBED``."""
    return _FAMILIES.get(type(n_heads))


def slot_store_shapes(params: Dict[str, Any], n_heads, n_slots: int,
                      max_len: int):
    """The shapes of the two per-slot stores an engine keeps for this tree,
    each a shape or a tuple of shapes, a slot at ``[slot]`` of each. What
    stands for ``n_heads`` says which family the tree is (:func:`family`):
    an int for the GPT-2 block (K and V by head, ``(S, L*H, max_len, hd)``
    each), else its family's ``store_shapes``."""
    fam = family(n_heads)
    if fam is not None:
        return fam.store_shapes(params, n_slots, max_len, n_heads)
    n_layers = stack_shape(params["wqkv"])[0]
    hd = params["embed"].shape[1] // n_heads
    shape = (n_slots, n_layers * n_heads, max_len, hd)
    return shape, shape


def lm_step_slots(params: Dict[str, Any], tokens: jax.Array,
                  kcaches: jax.Array, vcaches: jax.Array, poss: jax.Array,
                  n_heads, active: "jax.Array | None" = None, lane=None):
    """:func:`lm_decode_step_slots` for whichever family the tree is (see
    :func:`family`), with a fifth result: the step's counts, int32 in the
    order of the family's ``COUNTS``, or None for the GPT-2 block."""
    fam = family(n_heads)
    if fam is not None:
        return fam.decode_step_slots(
            params, tokens, kcaches, vcaches, poss, n_heads, active, lane)
    return (*lm_decode_step_slots(params, tokens, kcaches, vcaches, poss,
                                  n_heads, active, lane), None)


# --------------------------------------------------------------------------- #
# Paged KV cache execution forms (serving/kv_cache.py page pools)
#
# The paged kernels do NOT reimplement attention. Each step GATHERS a
# slot's pages into the exact flat per-slot cache layout the contiguous
# kernels consume, runs the ONE shared `_lm_window` body, and
# SCATTERS back only the pages the step could have touched. Exactness
# paged-vs-contiguous is therefore by construction, not by a parallel
# implementation (tests/test_kv_paging.py pins it bit-for-bit).
#
# Static-shape discipline: `page_size` and the table width B (the
# pages-per-slot bound — a slot's view is B·page_size tokens, its
# effective max_len) are baked into the executable, so paging adds no
# new compile axis beyond the buckets the engine already has. The
# gathered view is a transient of S·B·page_size tokens — the engine
# sizes B to the slot-equivalent budget, which is what keeps "hundreds
# of queued requests" from meaning "hundreds of resident caches".
# --------------------------------------------------------------------------- #


def _paged_view(pool, table):
    """Gather one slot's pages into a contiguous flat cache view.

    pool: (n_pages+1, L·H, ps, hd); table: (B,) int32 page ids. Returns
    (L·H, B·ps, hd) — exactly the single-slot transport layout with
    max_len = B·ps, so `_lm_window` runs on it unchanged (it
    reads capacity from the cache shape). Table rows past the request's
    allocation hold the null page (id 0): their zeros are garbage the
    causal `live` mask never attends.
    """
    pages = pool[table]                              # (B, LH, ps, hd)
    b, lh, ps, hd = pages.shape
    return pages.transpose(1, 0, 2, 3).reshape(lh, b * ps, hd)


#: (pool, tables (S, B)) -> (S, L·H, B·ps, hd) — one batched gather
paged_view_slots = jax.vmap(_paged_view, in_axes=(None, 0))


def paged_touch_span(w: int, page_size: int, n_tables: int) -> int:
    """Pages a W-token window can touch at worst alignment (start at a
    page's last token): (w-1)//ps + 2, capped at the table width. Static
    — the scatter width is part of the executable, not data."""
    return min(n_tables, (w - 1) // page_size + 2)


def _writeback_window(view, table, p0, nt):
    """Slice the ``nt`` pages around write position ``p0`` out of a
    modified view. Returns (ids (nt,), pages (nt, L·H, ps, hd)). The
    start is left-clipped so the window stays inside the table; clipped
    windows re-write earlier pages with the unchanged bits they were
    gathered with — harmless, and it keeps ``nt`` static."""
    lh, m, hd = view.shape
    b = table.shape[0]
    ps = m // b
    pages = view.reshape(lh, b, ps, hd).transpose(1, 0, 2, 3)
    start = jnp.clip(jnp.asarray(p0).reshape(()) // ps, 0, b - nt)
    ids = jax.lax.dynamic_slice_in_dim(table, start, nt)
    win = jax.lax.dynamic_slice_in_dim(pages, start, nt, axis=0)
    return ids, win


def _paged_update(pool, view, table, p0, nt):
    """Scatter one slot's touched pages back into the pool."""
    ids, win = _writeback_window(view, table, p0, nt)
    return pool.at[ids].set(win)


def paged_update_slots(pool, views, tables, p0s, nt: int):
    """Scatter S slots' touched pages back in ONE pool write.

    Duplicate scatter indices are safe by the allocator's invariants:
    modified positions live in exclusively-owned pages (COW discipline),
    shared pages in a clipped window carry their unchanged gathered
    bits, and empty slots' zeroed tables collide only on the null page
    (never read). So last-writer-wins ambiguity never changes bits that
    anyone attends.
    """
    ids, wins = jax.vmap(
        lambda v, t, p: _writeback_window(v, t, p, nt))(views, tables, p0s)
    return pool.at[ids.reshape(-1)].set(
        wins.reshape((-1,) + wins.shape[2:]))


def lm_prefill_paged(params: Dict[str, jax.Array], window: jax.Array,
                     kpool: jax.Array, vpool: jax.Array, table: jax.Array,
                     pos0: jax.Array, true_len: jax.Array, n_heads: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Prefill a right-padded SUFFIX window directly into pages.

    The prefix-hit admission path: positions 0..pos0-1 already hold
    valid K/V in shared pages (radix hit), so only the suffix is
    computed. window: (1, Wb) padded to a bucket with ``true_len`` real
    tokens; table: (B,) page ids. The causal row structure of the
    verify-window body gives padded-prompt masking for free: the
    returned logits row ``true_len - 1`` attends exactly columns <=
    pos0 + true_len - 1 (hit pages + the real suffix), never the padded
    rows' garbage — the same overwrite-before-visible contract as
    ``lm_prefill_masked``, relocated to pos0.

    Returns (logits (1, vocab), kpool', vpool', pos = pos0 + true_len).
    """
    with jax.default_matmul_precision(_PRECISION):
        p0 = jnp.asarray(pos0).reshape(()).astype(jnp.int32)
        tl = jnp.asarray(true_len).reshape(()).astype(jnp.int32)
        kv = _paged_view(kpool, table)
        vv = _paged_view(vpool, table)
        logits, kv, vv, _ = _lm_verify_window(
            params, window, kv, vv, p0.reshape(1), n_heads)
        last = jax.lax.dynamic_index_in_dim(logits[0], tl - 1, axis=0,
                                            keepdims=False)
        nt = paged_touch_span(window.shape[1], kpool.shape[2],
                              table.shape[0])
        kpool = _paged_update(kpool, kv, table, p0, nt)
        vpool = _paged_update(vpool, vv, table, p0, nt)
        return last[None], kpool, vpool, (p0 + tl).reshape(1)


def lm_verify_window_paged(params: Dict[str, jax.Array], tokens: jax.Array,
                           kpool: jax.Array, vpool: jax.Array,
                           tables: jax.Array, poss: jax.Array, n_heads: int
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array]:
    """Verify windows for S slots against paged caches: gather each
    slot's view, run :func:`lm_verify_window_slots` on the views,
    scatter back the touched pages.
    tokens: (S, W); tables: (S, B); poss: (S, 1). Returns (logits
    (S, W, vocab), kpool', vpool', poss+W). Slots past their view
    capacity B·ps NaN-poison their own row, same contract as the
    contiguous form."""
    kviews = paged_view_slots(kpool, tables)
    vviews = paged_view_slots(vpool, tables)
    logits, kviews, vviews, poss2 = lm_verify_window_slots(
        params, tokens, kviews, vviews, poss, n_heads)
    nt = paged_touch_span(tokens.shape[1], kpool.shape[2],
                          tables.shape[1])
    p0s = poss[:, 0]
    kpool = paged_update_slots(kpool, kviews, tables, p0s, nt)
    vpool = paged_update_slots(vpool, vviews, tables, p0s, nt)
    return logits, kpool, vpool, poss2


def lm_decode_step_paged(params: Dict[str, jax.Array], tokens: jax.Array,
                         kpool: jax.Array, vpool: jax.Array,
                         tables: jax.Array, poss: jax.Array, n_heads: int
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """One decode step for S slots against paged caches — the W=1 case
    of :func:`lm_verify_window_paged`, mirroring how the contiguous
    `lm_decode_step_slots` is the W=1 verify window. tokens: (S, 1, 1)."""
    return lm_verify_window_paged(
        params, tokens[:, :, 0], kpool, vpool, tables, poss, n_heads)


def empty_cache(n_layers: int, batch: int, n_heads: int, max_len: int,
                head_dim: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(kcache, vcache, pos) zero state in the flat transport layout."""
    flat = (n_layers * batch * n_heads, max_len, head_dim)
    return (np.zeros(flat, np.float32), np.zeros(flat, np.float32),
            np.zeros((1,), np.int32))


def make_causal_lm(vocab: str = "256", dim: str = "64", heads: str = "4",
                   layers: str = "2", max_len: str = "128",
                   batch: str = "1", seed: str = "0",
                   **_: Any) -> ModelBundle:
    V, D, H, L = int(vocab), int(dim), int(heads), int(layers)
    M, B = int(max_len), int(batch)
    if D % H:
        raise ValueError(f"causal_lm: dim={D} not divisible by heads={H}")
    hd = D // H
    params = init_causal_lm(jax.random.PRNGKey(int(seed)), V, D, H, L, M)

    def apply(p, token, kcache, vcache, pos):
        return lm_decode_step(p, token.astype(jnp.int32), kcache, vcache,
                              pos, H)

    flat = L * B * H
    in_info = TensorsInfo.from_strings(
        f"1:{B},{hd}:{M}:{flat},{hd}:{M}:{flat},1",
        "int32,float32,float32,int32")
    out_info = TensorsInfo.from_strings(
        f"{V}:{B},{hd}:{M}:{flat},{hd}:{M}:{flat},1",
        "float32,float32,float32,int32")
    return ModelBundle(
        "causal_lm", apply, params=params,
        in_info=in_info, out_info=out_info,
        metadata={"vocab": V, "dim": D, "heads": H, "layers": L,
                  "max_len": M, "head_dim": hd, "batch": B})


register_model("causal_lm", make_causal_lm)
