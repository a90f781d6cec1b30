"""Distributed continuous batching: the slot engine over a TP mesh.

`TPLMEngine` keeps `LMEngine`'s scheduler — queues, slots, chunking,
admission, retirement, sampling controls — and swaps the two device
kernels for mesh-sharded ones: the per-slot KV caches shard by
attention head over the mesh's model axis (`parallel/tp_decode.py`
layout), and each decode-chunk step runs the shared TP token step
(`tp_token_step` — one definition of the mask/psum/cache semantics for
every TP consumer) inside one `shard_map` program vmapped over slots.
A model whose serving cache exceeds one chip's HBM gets continuous
batching across the slice with the SAME outputs: greedy and sampled
streams match the single-device engine token-for-token (sampling runs
on the replicated psum'd logits with the same fold_in(seed, consumed)
keys, so the key schedule never sees the mesh).

Executable sharing follows the module-level-kernel convention stated in
lm_engine.py: the prefill/chunk kernels are built by lru_cached module
functions keyed on (mesh, axis, shapes), so a second engine over the
same mesh and model shapes compiles nothing, and the sharded KV stores
are donated through each chunk (in-place update, no copy).

Prefill runs TENSOR-PARALLEL too (parallel/tp_prefill.py): each
admission computes QKV for the local heads only and emits the cache
directly in the head-major TP layout — no replicated prompt forward,
no relayout step. Speculative decoding composes with the mesh as well
(`_tp_verify_fn`: W-token windows through the shared tp_window_step,
acceptance on the replicated logits) — the full serving matrix
(greedy/sampled/speculative x float/w8a8) runs single-device or
sharded with identical outputs.

The reference has no distributed serving of any kind (SURVEY §2.3/§2.5:
stateless per-buffer invokes + TCP offload of whole buffers).

Observability rides the inherited scheduler unchanged: the
serving.request / admission_wait / prefill / compile / decode spans
(obs/tracing.py) are opened by LMEngine's submit/_admit/_retire_if_done
hooks, which this class does not override — a mesh-sharded engine
reports the same trace shape as the single-device one, with
``engine="tp"`` in the span attrs via `_engine_label`. The same holds
for the health model (obs/health.py): `_init_health` registers a
``serving.engine:tp`` component (admission-stall watchdog input) and a
"first bucket compiled" readiness condition under ``engine:tp``, so
/healthz and /readyz cover the sharded engine with zero TP-specific
code — and for fleet federation (obs/fleet.py): a TP worker's pushes
carry the same engine="tp" series and remote-parented spans as any
other instance, so the aggregator needs no sharding awareness either.
Deadline load shedding (resilience/policy.py) is inherited the same
way: submit/_admit shed past-deadline requests before any sharded
prefill is dispatched, emitting ``resilience.shed`` with engine="tp".
The profiler (obs/profile.py) is inherited too: _admit/_decode's
``ENGINE_HOOK`` call sites record prefill/decode/verify intervals and
batch occupancy with ``_engine_label`` = "tp", so a sharded engine gets
its own ``nnstpu_profile_mfu_ratio{engine="tp"}`` / roofline gauges and
serving lanes in ``/debug/profile`` with zero TP-specific code (param
count for the FLOPs model comes from the engine's sharded tree — leaf
``.size`` is the GLOBAL logical size, so the MFU denominator is still
the whole model).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import causal_lm
from ..ops.int8 import stack_shape
from ..parallel.ring import _shard_map
from ..parallel.tp_decode import (strip_device_leaves, tp_param_specs,
                                  tp_shard_params, tp_token_step,
                                  tp_window_step)
from ..parallel.tp_prefill import make_tp_prefill
from . import sampling
from .lm_engine import (LMEngine, _accept_from_window, _conf_from_row,
                        _slot_insert)

__all__ = ["TPLMEngine"]


@functools.lru_cache(maxsize=None)
def _tp_prefill_fn(mesh: Mesh, axis: str, n_heads: int, max_len: int):
    """Shared TP prefill callable per (mesh, geometry) — the same
    executable-sharing convention as _chunk_fn."""
    return make_tp_prefill(n_heads, max_len, mesh, axis)


def _slot_shard_view(tp, kc, vc, n_heads, hn, max_len):
    """Per-device preamble every slot kernel shares: strip the device
    axis from the weight leaves and view the slot caches in the logical
    (S, L, 1, hn, max_len, hd) layout. Paired with _slot_shard_flat."""
    tp = strip_device_leaves(tp)
    kc, vc = kc[:, 0], vc[:, 0]            # (S, L*hn, M, hd)
    L = stack_shape(tp["wq"])[0]
    hd = stack_shape(tp["wq"])[1] // n_heads
    S = kc.shape[0]
    kc = kc.reshape(S, L, 1, hn, max_len, hd)
    vc = vc.reshape(S, L, 1, hn, max_len, hd)
    return tp, (kc, vc), (L, hd)


def _slot_shard_flat(kc, vc, L, hn, max_len, hd):
    """Inverse of _slot_shard_view's cache reshape: back to the sharded
    transport layout (S, 1, L*hn, max_len, hd)."""
    S = kc.shape[0]
    kc = kc.reshape(S, 1, L * hn, max_len, hd)
    vc = vc.reshape(S, 1, L * hn, max_len, hd)
    return kc, vc


@functools.lru_cache(maxsize=None)
def _chunk_fn(mesh: Mesh, axis: str, n_heads: int, max_len: int,
              n_steps: int, quantized: bool = False):
    """Build the jitted TP decode-chunk executable for these shapes —
    shared by every TPLMEngine over the same mesh/model geometry."""
    n = mesh.shape[axis]
    hn = n_heads // n

    def per_device(tp, tokens, kc, vc, pos, skeys, temp, topk, topp):
        tp, (kc, vc), (L, hd) = _slot_shard_view(
            tp, kc, vc, n_heads, hn, max_len)
        S = tokens.shape[0]

        def slot_step(tok, kc_s, vc_s, p):
            # tok (1, 1); kc_s (L, 1, hn, M, hd); psums ride vmap
            logits, kc_s, vc_s = tp_token_step(
                tp, tok, kc_s, vc_s, jnp.asarray(p).reshape(()),
                n_heads=n_heads, hn=hn, max_len=max_len, axis=axis)
            return logits[0], kc_s, vc_s, (p.reshape(()) + 1).reshape(1)

        def one(carry, _):
            tokens, kc, vc, pos = carry
            logits, kc, vc, pos = jax.vmap(slot_step)(
                tokens, kc, vc, pos)
            # logits (S, V) are replicated (post-psum identical on
            # every device) — sampling/argmax therefore agree too

            def sampled(lg):
                keys = sampling.step_keys(skeys, pos[:, 0])
                return sampling.sample_logits(lg, keys, temp, topk, topp)

            def greedy(lg):
                return jnp.argmax(lg, -1).astype(jnp.int32)

            nxt = jax.lax.cond(
                jnp.all(temp <= 0.0), greedy, sampled, logits)
            return (nxt[:, None, None], kc, vc, pos), nxt

        (tokens, kc, vc, pos), outs = jax.lax.scan(
            one, (tokens, kc, vc, pos), None, length=n_steps)
        kc, vc = _slot_shard_flat(kc, vc, L, hn, max_len, hd)
        return tokens, kc, vc, pos, outs.T

    spec_dev = P(None, axis)
    in_specs = (tp_param_specs(axis, quantized),
                P(), spec_dev, spec_dev, P(), P(), P(), P(), P())
    out_specs = (P(), spec_dev, spec_dev, P(), P())
    return jax.jit(_shard_map(per_device, mesh, in_specs=in_specs,
                              out_specs=out_specs),
                   donate_argnums=(1, 2, 3, 4))


@functools.lru_cache(maxsize=None)
def _tp_verify_fn(mesh: Mesh, axis: str, n_heads: int, max_len: int,
                  w: int, quantized: bool = False):
    """Build the jitted TP verify-chunk executable: W-token windows for
    all slots through `tp_window_step` (the same shared TP layer math
    as the decode chunk), acceptance via the same `_accept_from_window`
    as the single-device engine — speculative decoding composed with
    the mesh."""
    n = mesh.shape[axis]
    hn = n_heads // n

    def per_device(tp, tokens_in, kc, vc, pos):
        tp, (kc, vc), (L, hd) = _slot_shard_view(
            tp, kc, vc, n_heads, hn, max_len)
        S = tokens_in.shape[0]

        def slot_window(toks, kc_s, vc_s, p):
            logits, kc_s, vc_s = tp_window_step(
                tp, toks[None], kc_s, vc_s, jnp.asarray(p).reshape(()),
                n_heads=n_heads, hn=hn, max_len=max_len, axis=axis)
            return logits[0], kc_s, vc_s, (p.reshape(()) + w).reshape(1)

        logits, kc, vc, pos_w = jax.vmap(slot_window)(
            tokens_in, kc, vc, pos)
        # logits replicated post-psum: acceptance agrees on every device
        carried, pos_m, greedy, m = _accept_from_window(
            tokens_in, logits, pos_w)
        kc, vc = _slot_shard_flat(kc, vc, L, hn, max_len, hd)
        return carried, kc, vc, pos_m, greedy, m

    spec_dev = P(None, axis)
    in_specs = (tp_param_specs(axis, quantized),
                P(), spec_dev, spec_dev, P())
    out_specs = (P(), spec_dev, spec_dev, P(), P(), P())
    return jax.jit(_shard_map(per_device, mesh, in_specs=in_specs,
                              out_specs=out_specs),
                   donate_argnums=(2, 3, 4))


class TPLMEngine(LMEngine):
    """Continuous-batching engine with the KV cache head-sharded over
    ``mesh[axis]``. Same public API and outputs as `LMEngine` —
    including ``enroll``/``unenroll`` sched.DeviceEngine tenancy, since
    ``step_iteration`` is inherited (the tenant label defaults to the
    overridden ``_engine_label`` "tp")."""

    #: serving metrics series carry engine="tp" so single-device and
    #: mesh-sharded engines are separable on one scrape endpoint
    _engine_label = "tp"
    #: its chunk body is parallel/tp_decode's: prompts are prefilled whole
    _lane_capable = False

    def __init__(self, params: Dict[str, Any], n_heads: int, max_len: int,
                 mesh: Mesh, axis: str = "model", **kw) -> None:
        n = mesh.shape[axis]
        fam = causal_lm.family(n_heads)
        if fam is not None:
            raise ValueError(
                "TPLMEngine (the mesh-sharded engine) cannot serve "
                f"{fam.DESCRIBED}: its decode body shards K and V by head")
        if n_heads % n:
            raise ValueError(f"n_heads={n_heads} not divisible by "
                             f"mesh axis {axis}={n}")
        if any(kw.get(k) for k in ("kv_page_size", "kv_pages",
                                   "kv_slot_pages", "kv_host_offload")):
            raise ValueError(
                "TPLMEngine does not support the paged KV cache (kv_* "
                "options): its slot caches shard by head over the mesh; "
                "use the single-device LMEngine for paging")
        # pin the contiguous path so the NNS_LM_KV_* environment (the
        # nns-launch flag transport) can never silently enable paging
        # on a sharded engine
        kw["kv_page_size"] = 0
        # set before super().__init__: _alloc_slot_caches reads these
        self.mesh, self.axis, self._n = mesh, axis, n
        super().__init__(params, n_heads, max_len, **kw)
        self._tp = tp_shard_params(params, n_heads, mesh, axis)
        # self.params stays the caller's (host/unplaced) tree — used
        # only for shape introspection; replicating the full unsharded
        # weights would cost n x the sharded HBM footprint, defeating
        # the regime this engine exists for. All compute paths consume
        # self._tp (decode chunks AND the TP prefill).
        rep = NamedSharding(mesh, P())
        for name in ("_tokens", "_pos", "_skeys", "_temp", "_topk",
                     "_topp"):
            setattr(self, name, jax.device_put(
                np.asarray(getattr(self, name)), rep))

    # -- device-layout hooks ---------------------------------------------- #

    def _alloc_slot_caches(self):
        # sharded from birth: the unsharded (S, L*H, M, hd) zeros the
        # base class would allocate may not FIT one device in the
        # regime this engine exists for
        (_, lh, _, hd), _ = causal_lm.slot_store_shapes(
            self.params, self.n_heads, self.n_slots, self.max_len)
        shape = (self.n_slots, self._n, lh // self._n, self.max_len, hd)
        dev = NamedSharding(self.mesh, P(None, self.axis))
        zero = functools.partial(jnp.zeros, dtype=jnp.float32)
        return (jax.device_put(zero(shape), dev),
                jax.device_put(zero(shape), dev))

    def _prefill_into(self, slot, padded, true_len, skey, temp, tk, tp,
                      want_conf=False):
        # head-sharded prompt forward; the cache arrives already in the
        # TP transport layout. First-token sampling keys match the base
        # engine's (fold_in(seed, consumed)) on the replicated logits
        logits, kc_tp, vc_tp, pos = _tp_prefill_fn(
            self.mesh, self.axis, self.n_heads, self.max_len)(
            self._tp, jnp.asarray(padded), jnp.int32(true_len))
        first = sampling.sample_row(
            logits[0], jax.random.fold_in(skey, jnp.int32(true_len)),
            temp, tk, tp)
        sl = jnp.int32(slot)
        self._kc = _slot_insert(self._kc, kc_tp, sl)
        self._vc = _slot_insert(self._vc, vc_tp, sl)
        self._pos = _slot_insert(self._pos, pos, sl)
        if want_conf:
            # the psum'd logits are replicated, so the confidence triple
            # (obs/quality) computes eagerly on the local shard's view
            return first, _conf_from_row(logits[0])
        return first

    def _run_chunk(self, n_steps: int):
        with jax.default_matmul_precision("float32"):
            self._tokens, self._kc, self._vc, self._pos, outs = \
                _chunk_fn(self.mesh, self.axis, self.n_heads,
                          self.max_len, n_steps,
                          quantized="wo_s" in self._tp)(
                    self._tp, self._tokens, self._kc, self._vc,
                    self._pos, self._skeys, self._temp, self._topk,
                    self._topp)
        return outs

    def _kv_rows_asked(self, active, n: int) -> int:
        # parallel/tp_decode.tp_window_step attends the whole max_len
        # axis of every slot (ROADMAP C3)
        return n * self.n_slots * self.max_len

    def _run_verify(self, tokens_in):
        with jax.default_matmul_precision("float32"):
            return _tp_verify_fn(self.mesh, self.axis, self.n_heads,
                                 self.max_len, int(tokens_in.shape[1]),
                                 quantized="wo_s" in self._tp)(
                self._tp, jnp.asarray(tokens_in), self._kc, self._vc,
                self._pos)
