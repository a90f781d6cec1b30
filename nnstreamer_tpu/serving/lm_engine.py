"""Continuous batching for causal-LM generation.

The TPU-native answer to LM serving throughput: S fixed cache slots, one
compiled batched decode step (``lm_decode_step_slots`` — the slots as
the batch of the single-stream body), and a host-side iteration-level
scheduler that admits queued prompts into free slots the moment they
open. Decode work never waits for a whole batch to finish (the
static-batch failure mode): a stream that completes frees its slot at
the next iteration boundary and the next prompt prefills into it while
the other slots keep decoding.

XLA-shaped design decisions:
- **Static shapes everywhere.** The slot axis S, cache capacity
  ``max_len``, and chunk sizes are compile-time constants; per-slot
  write positions and liveness are traced VALUES (masks/scatters), so
  the whole serving loop reuses a handful of cached executables.
- **Prefill inside the decode step.** The contiguous engine carries a
  waiting prompt through its decode chunks, a window of ``LANE_ROWS``
  rows a step under the decode rows (the prompt lane: ``_chunk_scan``,
  ``causal_lm._lm_window``): a decode step is bound by HBM and a prefill
  by the MXU, and in one pass they share each weight's one read.
  Admission is host work and stops no stream.
- **Bucketed prefill** for the engines that keep a whole-prompt program
  (paged, mesh-sharded, speculative). Prompts are right-padded to
  a power-of-two bucket and prefilled with ``lm_prefill_masked`` — one
  compile per bucket, exact by masking (padded K/V slots are provably
  overwritten before any step can attend to them).
- **Chunked decode.** Between scheduler interventions the engine runs
  ``chunk`` decode steps as ONE jitted ``lax.scan`` (greedy argmax fed
  back on-device), so host round-trips per generated token are 1/chunk.
  A stream finishing mid-chunk wastes at most chunk-1 slot-steps (its
  discarded tokens are garbage only to itself — slot isolation is by
  vmap construction). ``chunk=1`` gives lowest admission latency;
  larger chunks amortize dispatch (through a high-RTT link they are the
  difference between RTT-bound and compute-bound serving).
- **One chunk ahead.** The contiguous engine with the lane dispatches
  chunk k+1 before it reads chunk k (``_decode``): the plan of a chunk
  needs positions and lengths the host can count, not the last tokens,
  so the device has its next program queued when one ends. An end the
  tokens decide (an EOS) is found a chunk late and that chunk's columns
  for the request are dropped; ``_drain`` reads what is in flight for
  whoever looks from outside the loop. An end the host can count (a
  request's length) frees its slot when the chunk that ends it is
  dispatched, so the next prompt's first window rides the chunk behind
  that one (``_release``).
- **Paged KV cache (opt-in: ``kv_page_size > 0``).** The per-slot
  contiguous stores are replaced by one shared page pool
  (serving/kv_cache.py): admission is gated on page availability
  instead of slot-sized reservations (so the request backlog is bounded
  by memory actually used, not slots x max_len), prompts sharing a
  prefix share its device pages (radix lookup + copy-on-write), and
  each jitted step gathers a slot's pages into the exact contiguous
  layout, runs the SAME kernels, and scatters back only the touched
  pages — greedy outputs stay bit-identical to the contiguous path
  (tests/test_kv_paging.py). ``kv_slot_pages`` bounds a slot's gathered
  view (its effective max_len), which is what keeps S slots' transient
  views inside a slot-equivalent memory budget.

Greedy-exactness contract: every stream's output matches isolated
single-stream generation token-for-token regardless of what shares the
batch, when it was admitted, or the chunk size (tests/test_lm_serving.py).

The reference has no analog (its `/root/reference/gst/nnstreamer/
tensor_filter/` serves stateless per-buffer invokes); this composes with
the pipeline via the query layer: a serversrc feeding prompts into an
engine-backed worker, generated sequences flowing back per request.
"""

from __future__ import annotations

import gc
import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tune as _tune
from ..core.log import logger
from ..models import causal_lm
from ..obs import diag as _diag
from ..obs import events as _events
from ..obs import health as _health
from ..obs import metrics as _obs
from ..obs import profile as _profile
from ..obs import quality as _quality
from ..obs import slo as _slo
from ..obs import tracing as _tracing
from ..ops.int8 import stack_shape
from ..ops.pallas import decode_attention
from ..resilience import policy as _rp
from . import sampling
from .kv_cache import PagedKVCache


log = logger("serving")


def _env_int(name: str) -> Optional[int]:
    """Parse an optional integer env knob; empty/unset -> None, junk
    raises with the variable named (typo-proof, like NNS_TPU_CHAOS)."""
    v = os.environ.get(name, "")
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}")


#: disaggregated-serving roles (serving/disagg.py): "prefill" engines
#: run chunked prefill only and export finished KV pages; "decode"
#: engines accept imported pages and decode (they can still re-prefill
#: from scratch on transfer failure); "unified" is the classic both-
#: phases engine and the default
ROLES = ("unified", "prefill", "decode")

#: bound on the per-engine session→token-path table behind live
#: migration (LRU-evicted; an evicted session migrates via the
#: re-prefill absorb path instead of a page shipment)
SESSION_PATHS_LIMIT = 256

#: the phases of one iteration (``serving.<name>`` spans, ``<name>_s``
#: counters; docs/observability.md has the table of what each covers);
#: ``device_wait`` and ``readback`` lie inside ``first_token_wait`` and
#: ``decode_wait``
STEP_PHASES = ("step", "admit", "admit_host", "prefill_dispatch",
               "slot_insert", "first_token_wait", "decode_dispatch",
               "decode_wait", "device_wait", "readback", "retire")
_PHASE_KEYS = tuple(f"{name}_s" for name in STEP_PHASES)

#: weak registry of every constructed engine — `nns-launch` walks it at
#: exit to print per-engine KV summaries without threading a handle
#: through the pipeline graph
_LIVE_ENGINES: "weakref.WeakSet[LMEngine]" = weakref.WeakSet()


def _copy_step(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {**rec, "phases": dict(rec["phases"]), "wait": dict(rec["wait"]),
            "admitted": list(rec["admitted"]), "gc": list(rec["gc"])}


def live_engines() -> List["LMEngine"]:
    """Engines constructed in this process and still alive (weak set —
    collected engines drop out). Order is unspecified."""
    return list(_LIVE_ENGINES)


def next_pow2_bucket(n: int, lo: int = 16) -> int:
    """Smallest power of two >= n (floored at ``lo``): the default
    prompt-length bucketing — compile count is log2(max_len) worst case."""
    b = lo
    while b < n:
        b *= 2
    return b


#: the jitted kernels live at module level (static args, not closures) so
#: their executable caches are shared by every LMEngine instance — a
#: second engine over the same model shapes compiles nothing


@partial(jax.jit, static_argnames=("n_heads", "max_len"))
def _prefill_admit(params, padded, true_len, skey, temp, top_k, top_p,
                   n_heads, max_len):
    logits, kc, vc, pos = causal_lm.lm_prefill_masked(
        params, padded, true_len, n_heads, max_len)
    # the first token is emitted having consumed true_len prompt tokens
    first = sampling.sample_row(
        logits[0], jax.random.fold_in(skey, true_len), temp, top_k, top_p)
    return first, kc, vc, pos


def _conf_from_row(row):
    """Model-confidence signals from one logits row: Shannon entropy
    (nats) of the softmax, top-1 probability, and the top-1/top-2
    probability margin — the per-request escalation signal obs/quality
    records at retirement. Returns a (3,) float32 array."""
    p = jax.nn.softmax(row.astype(jnp.float32))
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0))
    top2 = jax.lax.top_k(p, 2)[0]
    return jnp.stack([ent, top2[0], top2[0] - top2[1]])


@partial(jax.jit, static_argnames=("n_heads", "max_len"))
def _prefill_admit_conf(params, padded, true_len, skey, temp, top_k, top_p,
                        n_heads, max_len):
    """`_prefill_admit` plus confidence signals from the first-token
    logits — a distinct executable, compiled only when obs/quality is
    recording (the quality-off path never pays for the extra outputs)."""
    logits, kc, vc, pos = causal_lm.lm_prefill_masked(
        params, padded, true_len, n_heads, max_len)
    first = sampling.sample_row(
        logits[0], jax.random.fold_in(skey, true_len), temp, top_k, top_p)
    return first, kc, vc, pos, _conf_from_row(logits[0])


@partial(jax.jit, donate_argnums=(0,))
def _slot_insert(store, value, slot):
    # the caller always rebinds the result over `store`, so the old
    # buffer is donated — the multi-hundred-MB KV stores update in place
    # instead of being copied every admission
    return jax.lax.dynamic_update_slice(
        store, value[None].astype(store.dtype),
        (slot,) + (0,) * value.ndim)


#: prompt rows a decode step of the contiguous engine carries beside its
#: decode rows (the lane's width, P). Chosen on the chip from the mixed
#: step's microbenchmark at the production shape and three widths' runs
#: of the benchmark's cell (PERF.md §6, PR 29)
LANE_ROWS = 64


def _chunk_scan(params, tokens, kc, vc, pos, active, skeys, temp, top_k,
                top_p, n_heads, n_steps, lane=None):
    """The n_steps decode scan over per-slot caches — ONE body shared by
    the contiguous chunk and the paged chunk (which runs it on gathered
    page views; the step kernels read capacity from the cache shape, so
    the body is layout-agnostic). ``active`` (S,) bool: the slots that
    are decoding; the others are neither read nor written.

    With ``lane``, ``(plan (n_steps, 4 + P) int32, count () int32)``, the
    first ``count`` steps run (a traced number: ONE executable serves every
    length of a lane chunk, and whatever prompts wait) and each also
    prefills one window of P prompt rows: a row of the plan is ``[slot,
    pos0, count, last, tokens...]``, the prompt tokens ``pos0 .. pos0 + P -
    1`` (``count`` of them real) of the request that holds ``slot`` and is
    not decoding yet. A step holds S decode rows and P prompt rows in one
    pass over the weights (``causal_lm._lm_window``); the window's K/V rows
    are written in place into the slot's store. Where the window is the
    prompt's ``last``, the first token is sampled from its last real row
    under the slot's own key and controls (``fold_in(skey, true_len)``, as
    the whole-prompt prefill draws it), takes the slot's place in that
    step's ``outs``, ``pos`` becomes ``true_len`` and the slot decodes from
    the next step of the same loop: ``active`` rides the carry.
    Returns (tokens, kc, vc, pos, outs (S, n_steps), conf (n_steps, 3),
    counts): ``conf`` is the confidence triple of a ``last`` step's
    first-token logits, None without a lane; the columns of ``outs`` and
    rows of ``conf`` past ``count`` are zero. ``counts`` is the steps'
    routing, ``causal_lm.lm_step_slots``'s fifth result summed over the
    steps that ran, None for a tree without experts."""
    def one(carry, step):
        tokens, kc, vc, pos, active = carry
        if step is None:
            logits, kc, vc, pos, hits = causal_lm.lm_step_slots(
                params, tokens, kc, vc, pos, n_heads, active)
        else:
            slot, pos0, count, last = step[0], step[1], step[2], step[3] > 0
            logits, kc, vc, pos, hits = causal_lm.lm_step_slots(
                params, tokens, kc, vc, pos, n_heads, active,
                lane=(step[4:], slot, pos0, count))
            lane_row, logits = logits[-1, 0], logits[:-1]

        # pos is post-step = tokens consumed; keys derive from (seed,
        # consumed) only, so sampling is batch-composition-independent
        def sampled(lg):
            keys = sampling.step_keys(skeys, pos[:, 0])
            return sampling.sample_logits(
                lg[:, 0], keys, temp, top_k, top_p)  # (S,)

        def greedy(lg):
            return jnp.argmax(lg[:, 0], -1).astype(jnp.int32)

        # an all-greedy batch (the default) skips the sampler's
        # full-vocab top_k/softmax/cumsum in the decode hot loop
        nxt = jax.lax.cond(jnp.all(temp <= 0.0), greedy, sampled, logits)
        if step is None:
            return (nxt[:, None, None], kc, vc, pos, active), (nxt, hits)
        true_len = pos0 + count
        first = jax.lax.cond(
            last & (temp[slot] > 0.0),
            lambda row: sampling.sample_row(
                row, jax.random.fold_in(skeys[slot], true_len), temp[slot],
                top_k[slot], top_p[slot]),
            lambda row: jnp.argmax(row, -1).astype(jnp.int32), lane_row)
        conf = jax.lax.cond(last, _conf_from_row,
                            lambda row: jnp.zeros((3,), jnp.float32),
                            lane_row)
        join = last & (jnp.arange(nxt.shape[0]) == slot)
        nxt = jnp.where(join, first, nxt)
        pos = jnp.where(join[:, None], true_len, pos)
        return ((nxt[:, None, None], kc, vc, pos, active | join),
                (nxt, conf, hits))

    if lane is None:
        (tokens, kc, vc, pos, _), (outs, hits) = jax.lax.scan(
            one, (tokens, kc, vc, pos, active), None, length=n_steps)
        if hits is not None:
            hits = hits.sum(axis=0)
        return tokens, kc, vc, pos, outs.T, None, hits  # outs (S, n_steps)

    plan, count = lane
    fam = causal_lm.family(n_heads)
    counted = fam is not None

    def lane_step(i, carry):
        state, outs, confs, total = carry
        state, (nxt, conf, hits) = one(state, plan[i])
        if counted:
            total = total + hits
        return state, outs.at[i].set(nxt), confs.at[i].set(conf), total

    (tokens, kc, vc, pos, _), outs, confs, hits = jax.lax.fori_loop(
        0, count, lane_step,
        ((tokens, kc, vc, pos, active),
         jnp.zeros((n_steps, tokens.shape[0]), jnp.int32),
         jnp.zeros((n_steps, 3), jnp.float32),
         jnp.zeros((len(fam.COUNTS),), jnp.int32) if counted else None))
    return tokens, kc, vc, pos, outs.T, confs, hits


@partial(jax.jit, static_argnames=("n_heads", "n_steps"),
         donate_argnums=(1, 2, 3, 4))
def _decode_chunk(params, tokens, kc, vc, pos, active, skeys, temp, top_k,
                  top_p, lane=None, *, n_heads, n_steps):
    """``_chunk_scan`` over the contiguous stores: an executable a step
    count without a prompt lane (``lane`` None), and one with a lane for
    every count up to ``n_steps``. A tree without experts has no routing
    counts, and its programs return the six results they always did."""
    *out, counts = _chunk_scan(params, tokens, kc, vc, pos, active, skeys,
                               temp, top_k, top_p, n_heads, n_steps, lane)
    return tuple(out) if counts is None else (*out, counts)


@partial(jax.jit, static_argnames=("n_heads", "n_steps"),
         donate_argnums=(1, 2, 3, 5))
def _decode_chunk_paged(params, tokens, kpool, vpool, tables, pos, active,
                        skeys, temp, top_k, top_p, n_heads, n_steps):
    """Paged decode chunk: gather each slot's pages into a contiguous
    view ONCE per chunk, run the shared scan on the views (each step
    writes its rows in place, same as contiguous), scatter back only the
    pages an n_steps window can touch. The gather/scatter cost amortizes
    over the whole chunk, not per token."""
    kviews = causal_lm.paged_view_slots(kpool, tables)
    vviews = causal_lm.paged_view_slots(vpool, tables)
    p0s = pos[:, 0]
    tokens, kviews, vviews, pos, outs, _, _ = _chunk_scan(
        params, tokens, kviews, vviews, pos, active, skeys, temp, top_k,
        top_p, n_heads, n_steps)
    nt = causal_lm.paged_touch_span(
        n_steps, kpool.shape[2], tables.shape[1])
    kpool = causal_lm.paged_update_slots(kpool, kviews, tables, p0s, nt)
    vpool = causal_lm.paged_update_slots(vpool, vviews, tables, p0s, nt)
    return tokens, kpool, vpool, pos, outs


@partial(jax.jit, static_argnames=("n_heads",),
         donate_argnums=(2, 3, 4))
def _verify_chunk(params, tokens_in, kc, vc, pos, n_heads):
    """One speculative iteration: verify W-token windows for all slots,
    accept per-slot prefixes, and roll positions back past rejected
    drafts — one dispatch, like a decode chunk.

    tokens_in (S, W) = [carried token, draft_1..draft_{W-1}] per slot.
    Each slot accepts 1 + the longest draft prefix the model's own
    argmax confirms (row j logits match a sequential step's up to
    ~1e-7 matmul associativity with identical argmax —
    lm_verify_window). Greedy-only by design: the engine gates
    speculation to all-greedy active sets (a sampled stream can only
    ever accept one token per dispatch, which plain chunks serve
    strictly better), so no sampler runs here. Returns
    (carried' (S,1,1), kc, vc, pos+m, outs (S, W), m (S,)).
    """
    logits, kc, vc, pos_w = causal_lm.lm_verify_window_slots(
        params, tokens_in, kc, vc, pos, n_heads)
    carried, pos_m, greedy, m = _accept_from_window(
        tokens_in, logits, pos_w)
    return carried, kc, vc, pos_m, greedy, m


@partial(jax.jit, static_argnames=("n_heads",),
         donate_argnums=(2, 3, 5))
def _verify_chunk_paged(params, tokens_in, kpool, vpool, tables, pos,
                        n_heads):
    """Speculative verify against paged caches: the same acceptance
    logic on `lm_verify_window_paged`'s gathered-view logits. Rejected
    drafts' K/V land in pages the slot owns exclusively (or the null
    page past its reservation) and are overwritten before visible —
    the contiguous roll-back-by-pos invariant survives paging intact."""
    logits, kpool, vpool, pos_w = causal_lm.lm_verify_window_paged(
        params, tokens_in, kpool, vpool, tables, pos, n_heads)
    carried, pos_m, greedy, m = _accept_from_window(
        tokens_in, logits, pos_w)
    return carried, kpool, vpool, pos_m, greedy, m


@partial(jax.jit, static_argnames=("n_heads",), donate_argnums=(2, 3))
def _prefill_paged_admit(params, window, kpool, vpool, table, pos0,
                         true_len, skey, temp, top_k, top_p, n_heads):
    """Prefix-hit admission: prefill only the padded SUFFIX window into
    the slot's pages at pos0 = hit length. The sampling key folds in
    ``pos0 + true_len`` — the TOTAL tokens consumed — so a prefix-hit
    admission draws the same first token as a full prefill of the same
    prompt (the (seed, consumed) schedule is position-based, not
    dispatch-based)."""
    logits, kpool, vpool, pos = causal_lm.lm_prefill_paged(
        params, window, kpool, vpool, table, pos0, true_len, n_heads)
    first = sampling.sample_row(
        logits[0], jax.random.fold_in(skey, pos0 + true_len),
        temp, top_k, top_p)
    return first, kpool, vpool, pos


@partial(jax.jit, static_argnames=("n_heads",), donate_argnums=(2, 3))
def _prefill_paged_admit_conf(params, window, kpool, vpool, table, pos0,
                              true_len, skey, temp, top_k, top_p, n_heads):
    """`_prefill_paged_admit` plus confidence signals — the obs/quality
    variant of the prefix-hit admission kernel."""
    logits, kpool, vpool, pos = causal_lm.lm_prefill_paged(
        params, window, kpool, vpool, table, pos0, true_len, n_heads)
    first = sampling.sample_row(
        logits[0], jax.random.fold_in(skey, pos0 + true_len),
        temp, top_k, top_p)
    return first, kpool, vpool, pos, _conf_from_row(logits[0])


@partial(jax.jit, donate_argnums=(0, 1))
def _install_pages(kpool, vpool, kc, vc, table):
    """Scatter a freshly prefilled contiguous slot cache (the no-hit
    admission path reuses `_prefill_admit` unchanged) into the slot's
    pages. Table rows past the prompt's pages hold the null page —
    the padded tail's garbage K/V lands there, never in live pages."""
    lh, m, hd = kc.shape
    b = table.shape[0]
    ps = m // b
    kpages = kc.reshape(lh, b, ps, hd).transpose(1, 0, 2, 3)
    vpages = vc.reshape(lh, b, ps, hd).transpose(1, 0, 2, 3)
    return kpool.at[table].set(kpages), vpool.at[table].set(vpages)


def _accept_from_window(tokens_in, logits, pos_w):
    """Per-slot draft acceptance from a verify window's logits — ONE
    definition shared by the single-device and TP verify chunks.
    tokens_in (S, W); logits (S, W, V); pos_w (S, 1) post-window.
    Returns (carried (S,1,1), pos_m = pos+m, greedy (S, W), m (S,))."""
    w = tokens_in.shape[1]
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)      # (S, W)
    # draft token j (input col j, j>=1) is confirmed iff it equals the
    # model's output at col j-1 AND every earlier draft was confirmed
    ok = (tokens_in[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
    m = 1 + jnp.cumprod(ok, axis=-1).sum(-1)               # (S,) in 1..W
    pos_m = pos_w - w + m[:, None]                         # = pos + m
    carried = jnp.take_along_axis(greedy, m[:, None] - 1, axis=1)
    return carried[:, :, None], pos_m, greedy, m


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray          # (T,) int32
    max_new: int
    eos: Optional[int]
    temperature: float = 0.0    # <= 0 → greedy
    top_k: int = 0              # <= 0 → disabled
    top_p: float = 1.0          # >= 1 → disabled
    seed: int = 0
    out: List[int] = field(default_factory=list)
    done: bool = False
    #: tokens a chunk that is dispatched and not read yet gives the
    #: request if none of them ends it: what the plan of the chunk behind
    #: it counts with
    due: int = 0
    #: the first token and the tokens of the same chunk behind it, read
    #: and not handed out: they go into ``out`` with the next chunk's
    held: List[int] = field(default_factory=list)
    t_submit: float = 0.0       # monotonic stamp for the TTFT histogram
    #: resilience.policy.Deadline (or None): checked at submit and again
    #: at admission — expired work is shed, not prefilled
    deadline: Any = None
    #: session affinity key (query.router consistent-hashes it so this
    #: engine keeps seeing the session whose prefix cache it holds);
    #: informational here — tagged on the request span and available
    #: to KV policies, never used for scheduling
    session: Optional[str] = None
    #: kv_cache.PageLease while admitted under paging (None otherwise):
    #: the request's page-table bookkeeping, released at retirement
    kv_lease: Any = None
    #: (entropy, top1_prob, top2_margin) from the first-token logits —
    #: set at admission only while obs/quality records, read at retire
    conf: Any = None
    # tracing (None when tracing is off at submit time): the request
    # span parents admission-wait / prefill / compile / decode children
    span: Any = None            # serving.request — submit → retire
    wait_span: Any = None       # serving.admission_wait — submit → admit
    prefill_span: Any = None    # serving.prefill, while the lane holds it
    decode_span: Any = None     # serving.decode — admit → retire


@dataclass
class _Chunk:
    """One decode chunk from its dispatch to its read (``_dispatch_chunk``,
    ``_read_chunk``): what the host planned for it, so that the chunk
    behind it can be planned and dispatched before its tokens are read."""
    n: int                      # steps
    outs: Any                   # (S, n) tokens, on the device until read
    lane: bool                  # whether its steps carried prompt windows
    ahead: bool                 # dispatched while another was unread
    #: when the device could begin it: its dispatch, or the end of the
    #: wait for the chunk it was queued behind
    start_ns: int
    active: int                 # slots that decoded from the first step
    slot_steps: int
    kv_rows: int
    #: the steps' routing counts, on the device until read (None for a
    #: tree without experts)
    counts: Any
    #: slot -> (request, the column its decode tokens start at, how many
    #: of them it keeps if none ends it); a slot whose prompt ended in
    #: step j of this chunk starts at j + 1, and ``firsts`` has its j,
    #: the column of its first token
    cols: Dict[int, Tuple[_Request, int, int]]
    firsts: Dict[int, int] = field(default_factory=dict)
    lane_tokens: int = 0
    conf: Any = None            # the lane's confidence triples, (n, 3)
    #: whether its step count was cut at the earliest end by length
    cut: bool = False


class LMEngine:
    """Continuous-batching engine over one causal LM.

    params/n_heads/max_len as for `models.causal_lm`; ``n_slots`` is the
    decode batch (slot) count; ``chunk`` the decode steps per scheduler
    iteration. ``bucket`` maps a prompt length to its padded prefill
    length (defaults to power-of-two buckets capped at max_len) on the
    engines that prefill a whole prompt; the contiguous engine prefills
    through the prompt lane of its decode chunks and pads a prompt's last
    window only.

    Paged KV cache (serving/kv_cache.py): ``kv_page_size`` > 0 swaps
    the per-slot contiguous stores for a shared page pool of
    ``kv_pages`` pages with radix prefix sharing; ``kv_slot_pages``
    bounds one request's capacity (pages x page_size tokens, default
    max_len worth); ``kv_host_offload`` keeps evicted cold pages in
    host RAM for re-upload instead of recomputing. All four default
    from NNS_LM_KV_PAGE_SIZE / NNS_LM_KV_PAGES / NNS_LM_KV_SLOT_PAGES /
    NNS_LM_KV_OFFLOAD so `nns-launch --kv-page-size/--kv-pages` reach
    engines constructed anywhere; an explicit ``kv_page_size=0`` pins
    the contiguous path regardless of environment.
    """

    def __init__(self, params: Dict[str, Any], n_heads: int, max_len: int,
                 n_slots: int = 4, chunk: Optional[int] = None,
                 bucket=None, spec_draft: int = 0,
                 kv_page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 kv_slot_pages: Optional[int] = None,
                 kv_host_offload: Optional[bool] = None,
                 role: Optional[str] = None) -> None:
        # prefill/decode chunk: explicit wins; unset consults the
        # autotuner (store/model only — no sweep closure: constructing
        # an engine must never dispatch), else the hand-set 8
        #: the module of the tree's family where it is not the GPT-2 block
        #: (``causal_lm.family``): what stands for ``n_heads`` says which
        self._family = causal_lm.family(n_heads)
        heads = n_heads.n_heads if self._family else n_heads
        if chunk is None:
            chunk = 8
            tn = _tune.TUNE_HOOK
            if tn is not None:
                chunk = int(tn.pick(
                    "lm_chunk", _tune.device_kind(), "serving.lm",
                    _tune.shape_sig(("slots", n_slots),
                                    ("len", max_len),
                                    ("heads", heads)),
                    candidates=(4, 8, 16, 32), default=8))
        if n_slots < 1 or chunk < 1:
            raise ValueError("n_slots and chunk must be >= 1")
        # disaggregated-serving role: explicit kwarg wins, else the
        # NNS_LM_ROLE environment (the `nns-launch --role` transport),
        # else unified — same precedence as the NNS_LM_KV_* knobs
        r = role if role is not None \
            else (os.environ.get("NNS_LM_ROLE", "") or "unified")
        if r not in ROLES:
            raise ValueError(
                f"role must be one of {ROLES}, got {r!r}")
        self.role = r
        if spec_draft < 0 or spec_draft + 1 > max_len:
            raise ValueError("spec_draft must be in [0, max_len-1]")
        self.params = params
        self.n_heads = n_heads
        self.max_len = max_len
        self.n_slots = n_slots
        self.chunk = chunk
        #: speculative decoding: draft spec_draft tokens per iteration
        #: by prompt-lookup (trailing n-gram match in the stream's own
        #: history) and verify them in ONE dispatch (_verify_chunk).
        #: Greedy outputs stay bit-identical (tests/test_lm_spec.py);
        #: accepted-per-iteration rides text repetitiveness, so the win
        #: is workload-dependent where chunking's is unconditional —
        #: the two compose by falling back to chunks near capacity
        self.spec_draft = spec_draft
        self._bucket = bucket or (
            lambda n: min(next_pow2_bucket(n), max_len))
        # paged-KV config: explicit kwargs win; unset ones fall back to
        # the NNS_LM_KV_* environment (the nns-launch flag transport)
        ps = kv_page_size if kv_page_size is not None \
            else (_env_int("NNS_LM_KV_PAGE_SIZE") or 0)
        if ps == 0 and kv_page_size is None and _tune.TUNE_HOOK is not None \
                and (kv_pages is not None or _env_int("NNS_LM_KV_PAGES")):
            # a page budget was given without a page granularity: the
            # tuner owns it (store/fleet only — same no-dispatch rule
            # as the chunk knob). kv_page_size=0 explicit still pins
            # the contiguous path.
            cands = tuple(c for c in (16, 32, 64, 128, 256)
                          if c <= max_len and max_len % c == 0)
            if cands:
                dflt = 64 if 64 in cands else cands[0]
                ps = int(_tune.TUNE_HOOK.pick(
                    "lm_kv_page_size", _tune.device_kind(), "serving.lm",
                    _tune.shape_sig(("len", max_len),
                                    ("heads", heads)),
                    candidates=cands, default=dflt))
        if ps < 0:
            raise ValueError("kv_page_size must be >= 0 (0 = contiguous)")
        if self._family:
            self._refuse_family(params, ps, spec_draft, max_len)
        self._kv: Optional[PagedKVCache] = None
        self._m_slot = max_len  # one request's token capacity
        if ps:
            if max_len % ps:
                raise ValueError(
                    f"kv_page_size={ps} must divide max_len={max_len}")
            slot_pages = kv_slot_pages if kv_slot_pages is not None \
                else (_env_int("NNS_LM_KV_SLOT_PAGES") or max_len // ps)
            if not 1 <= slot_pages <= max_len // ps:
                raise ValueError(
                    f"kv_slot_pages={slot_pages} outside "
                    f"[1, max_len/page_size={max_len // ps}]")
            self._m_slot = slot_pages * ps
            if spec_draft + 1 > self._m_slot:
                raise ValueError(
                    f"spec_draft={spec_draft} needs kv_slot_pages * "
                    f"kv_page_size > spec_draft (got {self._m_slot})")
            n_pages = kv_pages if kv_pages is not None \
                else (_env_int("NNS_LM_KV_PAGES")
                      or n_slots * slot_pages)
            offload = kv_host_offload if kv_host_offload is not None \
                else os.environ.get("NNS_LM_KV_OFFLOAD", "") == "1"
            self._kv = PagedKVCache(
                stack_shape(params["wqkv"])[0], n_heads, ps, n_pages,
                params["embed"].shape[1] // n_heads,
                host_offload=bool(offload), label=self._engine_label)
            self._kv_slot_pages = slot_pages
            #: per-slot page tables, mirrored on host (the scheduler is
            #: the only writer); row entries past a request's allocated
            #: pages hold the null page 0
            self._table_host = np.zeros((n_slots, slot_pages), np.int32)
        #: rows a slot's length rounds up to in its attention kernel's
        #: reads (``stats['kv_rows_attended']``)
        self._kv_grain = (self._family.ATTENTION if self._family
                          else decode_attention).read_grain(self._m_slot)
        if self.role != "unified" and self._kv is None:
            # the page pool IS the transfer substrate: a prefill engine
            # has nothing to export and a decode engine nowhere to
            # splice imports without it
            raise ValueError(
                f"role={self.role!r} requires the paged KV cache "
                f"(set kv_page_size > 0)")
        # cross-backend KV-page imports (serving/disagg.py): docs land
        # here from the wire thread and are spliced by the scheduler
        # thread at the top of each iteration — PagedKVCache itself is
        # single-threaded by contract
        self._kv_imports: deque = deque()
        self._kv_imports_lock = threading.Lock()
        # device-resident slot state (leading axis = slot); cache
        # allocation is a hook so a mesh-sharded engine never
        # materializes the unsharded stores (serving/tp_engine.py);
        # the paged path has no per-slot stores at all — its K/V live
        # in the shared page pool
        self._tokens = jnp.zeros((n_slots, 1, 1), jnp.int32)
        self._kc = self._vc = None
        if self._kv is None:
            self._kc, self._vc = self._alloc_slot_caches()
        self._pos = jnp.zeros((n_slots, 1), jnp.int32)
        #: whether prompts are prefilled inside the decode chunks, a
        #: window of LANE_ROWS rows a step (`_chunk_scan`), or whole and
        #: padded by the engine's own prefill programs. It follows from
        #: what the engine is: the lane is the contiguous single-device
        #: engine's, chunked and continuous, over a store that whole
        #: windows tile
        self._lane = (self._lane_capable and self._kv is None
                      and spec_draft == 0 and max_len % LANE_ROWS == 0)
        #: slot -> the prompt tokens already given to the lane, for the
        #: slots whose request is still being prefilled, in the order
        #: they were admitted
        self._lane_at: Dict[int, int] = {}
        #: the lane's rows of the chunk last dispatched (`_plan_lane`), and
        #: the confidence triples its program returns
        self._lane_plan = self._lane_conf = self._chunk_counts = None
        #: the chunk that is dispatched and not read, where the engine
        #: runs a chunk ahead (`_runs_ahead`); None between iterations on
        #: every other engine, and on an engine that holds no request
        self._flight: Optional[_Chunk] = None
        # per-slot sampling controls (traced values — greedy and sampled
        # streams share one executable; see serving/sampling.py). A lane
        # engine keeps them on the host and hands them to every chunk;
        # the others keep them on the device and insert at admission
        xp = np if self._lane else jnp
        self._skeys = xp.zeros((n_slots, 2), xp.uint32)
        self._temp = xp.zeros((n_slots,), xp.float32)
        self._topk = xp.zeros((n_slots,), xp.int32)
        self._topp = xp.ones((n_slots,), xp.float32)
        # host-side scheduler state (incl. a per-slot position mirror:
        # positions are deterministic — true_len at admission, +n per
        # chunk — so the capacity cap never needs a blocking D2H read)
        self._pos_host: List[int] = [0] * n_slots
        self._slot_req: List[Optional[_Request]] = [None] * n_slots
        #: rid -> (slot, request) for the requests that gave their slot up
        #: when the chunk that ends them by length was dispatched, while
        #: that chunk is unread (``_release``)
        self._released: Dict[int, Tuple[int, _Request]] = {}
        self._queue: deque[_Request] = deque()
        self._finished: Dict[int, List[int]] = {}
        self._next_rid = 0
        # live-migration session state (fleet/migrate.py): the token
        # path each session last committed to the KV cache — what
        # export_session ships — plus the set frozen mid-migration
        # (their submits are refused so the router fails them over to
        # the re-pinned target). LRU-bounded; eviction only costs the
        # evicted session its migration warmth.
        self._session_paths: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self._frozen_sessions: set = set()
        # path snapshots taken AT freeze time: export_session ships the
        # snapshot, so a retire landing between freeze and export can
        # no longer move the exported path under the migrator's feet
        self._frozen_paths: Dict[str, np.ndarray] = {}
        # sessions whose migration was absorbed (resume_session): their
        # NEXT prefill re-derives state the fleet failed to ship, and
        # the diag critical path bills it as re_prefill, not compute
        self._reprefill_sessions: set = set()
        # sessions a crash-restore spliced a checkpoint into
        # (adopt_restored_session): their next prefill rides the
        # imported pages and diag bills it as restore, not re_prefill
        self._restored_sessions: set = set()
        # decode_steps/slot_steps/wasted_slot_steps account the CHUNK
        # path only; speculative iterations are accounted separately
        # by the spec_* keys — tokens from them are in tokens_out but
        # not in the slots x steps = kept + wasted chunk invariant
        self.stats = {"prefills": 0, "decode_steps": 0,
                      "slot_steps": 0, "wasted_slot_steps": 0,
                      # store rows the chunks' steps were asked to read
                      # (of decode_steps x slots x max_len at most)
                      "kv_rows_attended": 0,
                      # the prompt lane: decode steps that carried prompt
                      # rows, the rows they offered (LANE_ROWS a step)
                      # and the true prompt tokens among them
                      "lane_steps": 0, "lane_rows": 0, "lane_tokens": 0,
                      "tokens_out": 0,
                      "spec_iterations": 0, "spec_drafted": 0,
                      "spec_accepted": 0,
                      # iterations run and decode dispatches read (a
                      # chunk or a verify window); of the chunks, those
                      # dispatched while the one before was unread, and
                      # the slot-steps of those that were dropped because
                      # the tokens of the chunk before ended the request
                      "iterations": 0, "chunks": 0,
                      "chunks_ahead": 0, "ahead_dropped_slot_steps": 0,
                      # admissions into a slot whose request before still
                      # had tokens in a chunk not read (``_release``)
                      "admits_ahead": 0,
                      # chunks cut short to end where a request ends by
                      # length while a queue waited (``_dispatch_chunk``)
                      "chunks_cut": 0,
                      # wall of the dispatch phases that used an
                      # executable for the first time (compile, or its
                      # load from the cache)
                      "first_use_s": 0.0,
                      # submit -> slot granted, summed and the longest
                      "admission_wait_s": 0.0,
                      "admission_wait_max_s": 0.0}
        if self._family:
            # the counts of the steps read, counted inside the program
            # (lane rows included): for a family with experts the distinct
            # experts that got a row, a step and expert layer, and the
            # picks made; for the latent family the latent rows the steps
            # were asked to read, as kv_rows_attended counts K/V rows; for
            # the Gated DeltaNet family the slot-layers whose recurrent
            # state a step updated
            rows = getattr(self._family, "ROWS_ATTENDED", None)
            self.stats.update((key, 0) for key in
                              self._family.COUNTS + ((rows,) if rows else ()))
        # <phase>_s: seconds in each phase, added by obs/tracing.phase;
        # wait_<cause>_s: the parts of device_wait the witness told apart
        # (obs/tracing.classify_wait)
        self.stats.update((key, 0.0) for key in _PHASE_KEYS)
        self.stats.update((f"wait_{cause}_s", 0.0)
                          for cause in _tracing.WAIT_CAUSES)
        # one record an iteration: the last STEP_RING of them, and the
        # STEP_KEEP_SLOWEST slowest of the engine's life that used no
        # executable for the first time (a compile is slow by design)
        self._steps: deque = deque(maxlen=self.STEP_RING)
        self._slow_steps: List[Dict[str, Any]] = []
        self._steps_lock = threading.Lock()
        # sched.DeviceEngine tenancy (enroll()/unenroll()); None means
        # step_iteration runs direct — the usual zero-overhead gate
        self._sched_tenant = None
        self._sched_engine = None
        self._init_metrics()
        self._init_health()
        _LIVE_ENGINES.add(self)

    #: distinguishes engine kinds in the metric series; the TP engine
    #: overrides to "tp"
    _engine_label = "lm"

    #: whether this class's chunk program can carry a prompt lane; an
    #: engine with a decode body of its own says no
    _lane_capable = True

    #: iterations kept by recent_steps() / slowest_steps()
    STEP_RING = 1024
    STEP_KEEP_SLOWEST = 8
    #: an iteration that compiled nothing and lasted longer than this is
    #: logged as a stall with its record (seconds)
    STEP_STALL_S = 1.0

    def _init_metrics(self) -> None:
        """Register the serving metric families (obs subsystem). Handles
        are real whether or not collection is enabled — recording is the
        registry's cheap no-op when it is off. Depth-style gauges read
        through weakrefs at collection time so holding them never pins a
        retired engine's device caches."""
        import weakref

        reg = _obs.registry()
        lbl = self._engine_label
        self._m_streams = reg.counter(
            "nnstpu_serving_streams_total",
            "Streams admitted into slots / completed",
            ("engine", "event"))
        self._m_tokens = reg.counter(
            "nnstpu_serving_tokens_total",
            "Generated tokens across completed streams",
            ("engine",)).labels(lbl)
        self._m_ttft = reg.histogram(
            "nnstpu_serving_ttft_seconds",
            "Submit-to-first-token latency", ("engine",)).labels(lbl)
        self._m_tok_lat = reg.histogram(
            "nnstpu_serving_token_latency_seconds",
            "Per-token decode latency (chunk wall / steps, sampled "
            "once per chunk)", ("engine",)).labels(lbl)
        self._m_prefills = reg.counter(
            "nnstpu_serving_prefills_total",
            "Prompt prefills by padded bucket length",
            ("engine", "bucket"))
        self._m_compiles = reg.counter(
            "nnstpu_serving_prefill_compiles_total",
            "First-use prefill buckets (each is one XLA compile)",
            ("engine", "bucket"))
        #: executables this engine has used: prefill bucket keys, and
        #: ("chunk", n) / ("verify", w) for the decode programs
        self._seen_programs: set = set()
        # gauges sample the MOST RECENTLY constructed engine per label
        ref = weakref.ref(self)
        reg.gauge(
            "nnstpu_serving_active_slots",
            "Slots currently occupied by a live stream",
            ("engine",)).labels(lbl).set_function(
                lambda: sum(r is not None for r in ref()._slot_req)
                if ref() is not None else 0)
        reg.gauge(
            "nnstpu_serving_queue_depth",
            "Requests queued awaiting a free slot",
            ("engine",)).labels(lbl).set_function(
                lambda: len(ref()._queue) if ref() is not None else 0)

    def _init_health(self) -> None:
        """Register the engine's health component + warmed-readiness
        condition (obs/health.py). The watchdog's admission-stall rule
        reads ``oldest_wait_s`` from the probe; /readyz reads "first
        bucket compiled" from the readiness condition. Both go through
        weakrefs so health never pins a retired engine's caches, and
        both are skipped entirely (shared no-op component) while health
        is off."""
        import weakref

        lbl = self._engine_label
        ref = weakref.ref(self)

        def probe():
            eng = ref()
            if eng is None:
                return None
            oldest = min((r.t_submit for r in eng._queue), default=None)
            return {
                "queued": len(eng._queue),
                "active": sum(r is not None for r in eng._slot_req),
                "warmed": bool(eng._seen_programs),
                "oldest_wait_s": (time.monotonic() - oldest)
                if oldest is not None else 0.0,
            }

        self._hc = _health.component(
            f"serving.engine:{lbl}", kind="serving", probe=probe,
            attrs={"engine": lbl})
        _health.add_readiness(
            f"engine:{lbl}",
            lambda: (lambda e: None if e is None
                     else bool(e._seen_programs))(ref()))

    def _alloc_slot_caches(self):
        """Zero per-slot stores in the tree's layout
        (``causal_lm.slot_store_shapes``: (S, L·H, max_len, hd) twice for
        the GPT-2 block). Overridden by the mesh-sharded engine to
        allocate sharded-from-birth."""
        def zeros(shape):
            if isinstance(shape[0], tuple):
                return tuple(zeros(part) for part in shape)
            return jnp.zeros(shape, jnp.float32)

        return tuple(map(zeros, causal_lm.slot_store_shapes(
            self.params, self.n_heads, self.n_slots, self.max_len)))

    def _refuse_family(self, params, page_size: int, spec_draft: int,
                       max_len: int) -> None:
        """A tree of a family other than the GPT-2 block is served by the
        contiguous lane engine alone; whatever else was asked for is
        refused here, with its name, before anything could miscompute."""
        what = None
        if not self._lane_capable:
            what = ("an engine whose chunk program carries no prompt lane "
                    "(the mesh-sharded engine): its decode body attends K "
                    "and V by head")
        elif page_size:
            what = (f"the paged KV cache (kv_page_size={page_size}): pages "
                    "hold K and V by head, and none of this family's "
                    "stores")
        elif spec_draft:
            what = (f"speculative decoding (spec_draft={spec_draft}): the "
                    "verify window attends K and V by head")
        elif max_len % LANE_ROWS:
            what = (f"max_len={max_len}, no multiple of the prompt lane's "
                    f"{LANE_ROWS} rows: this family has no whole-prompt "
                    "prefill")
        elif any(getattr(leaf, "dtype", None) != jnp.float32
                 for leaf in jax.tree_util.tree_leaves(params)):
            what = ("a quantized tree (or any leaf that is not float32): "
                    "the w8a8 form exists for the GPT-2 block's GEMM "
                    "stacks only")
        if what is not None:
            raise ValueError(
                f"LMEngine cannot serve {self._family.DESCRIBED} with {what}")

    # -- public API ------------------------------------------------------- #

    def submit(self, prompt: Sequence[int], max_new: int,
               eos: Optional[int] = None, *, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               deadline: Any = None,
               session: Optional[str] = None) -> int:
        """Queue a generation request; returns its request id.

        ``temperature``/``top_k``/``top_p`` select the decoding mode per
        request (defaults = greedy, bit-identical to the pre-sampling
        engine). ``seed`` fixes the request's PRNG stream: the sampled
        output is reproducible and independent of batch composition
        (serving/sampling.py key schedule). ``deadline`` (a
        resilience.policy.Deadline) enables load shedding: a request
        whose deadline has already expired — at submit or later while
        still queued at admission — finishes empty immediately
        (``resilience.shed`` event + counter) instead of occupying a
        slot behind the admission-stall watchdog. ``session`` is the
        routing affinity key (query/router.py pins a session to one
        engine so its radix prefix cache keeps hitting): recorded on
        the request and its span, not a scheduling input.
        """
        p = np.asarray(prompt, np.int32).reshape(-1)
        if p.size < 1:
            self._reject("empty prompt")
            raise ValueError("empty prompt")
        if session is not None and str(session) in self._frozen_sessions:
            # mid-migration: the session's KV pages are in flight to
            # another backend — refusing here makes the router fail the
            # request over to the re-pinned target under its ORIGINAL
            # deadline instead of decoding against a torn cache
            self._reject("session frozen for migration")
            raise ValueError(
                f"session {session!r} is frozen for migration")
        if max_new < 1:
            self._reject("max_new must be >= 1")
            raise ValueError("max_new must be >= 1")
        if self.role == "prefill" and max_new != 1:
            # a prefill engine's product is the KV pages, not tokens:
            # the single generated token only proves exactness (it must
            # match what the decode backend regenerates from the
            # imported prefix)
            self._reject("prefill role accepts max_new=1 only")
            raise ValueError(
                f"role='prefill' engines run prefill only "
                f"(max_new must be 1, got {max_new})")
        if p.size + max_new - 1 > self.max_len:
            # the LAST generated token needs no cache slot, hence -1
            self._reject("prompt + max_new exceeds cache capacity")
            raise ValueError(
                f"prompt ({p.size}) + max_new ({max_new}) exceeds cache "
                f"capacity max_len={self.max_len}")
        if self._kv is not None:
            if p.size + max_new - 1 > self._m_slot:
                self._reject("prompt + max_new exceeds paged slot view")
                raise ValueError(
                    f"prompt ({p.size}) + max_new ({max_new}) exceeds "
                    f"paged per-request capacity kv_slot_pages * "
                    f"kv_page_size = {self._m_slot}")
            need = -(-(p.size + max_new - 1) // self._kv.page_size)
            if need > self._kv.n_pages:
                # would deadlock admission: even an empty pool could
                # never cover this request's reservation
                self._reject("request page budget exceeds pool")
                raise ValueError(
                    f"request needs {need} KV pages but the pool has "
                    f"only kv_pages={self._kv.n_pages}")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(
            rid, p, max_new, eos, temperature=float(temperature),
            top_k=int(top_k), top_p=float(top_p), seed=int(seed),
            t_submit=time.monotonic(), deadline=deadline,
            session=str(session) if session is not None else None)
        if deadline is not None and deadline.expired():
            # shed at the door: the caller's budget is already spent,
            # so queueing would only delay everyone behind it
            self._shed_request(req, "deadline expired at submit")
            return rid
        if _tracing.enabled():
            # parent on the caller's current context (an instrumented
            # element chain sets it) so an offloaded request joins the
            # pipeline's trace; without one this roots a fresh trace
            req.span = _tracing.start_span(
                "serving.request", parent=_tracing.current_context(),
                attrs={"engine": self._engine_label, "rid": rid,
                       "prompt_len": int(p.size), "max_new": int(max_new)})
            if req.session is not None:
                req.span.set_attribute("session", req.session)
            if req.span.recording and req.span.context.parent_id is not None:
                # remote-parented request (came in over the query wire):
                # mark the trace so fleet push exports the engine-side
                # spans — admission/prefill/decode join the client's
                # tree on the aggregator
                _tracing.store().mark_export(req.span.context.trace_id)
            req.wait_span = _tracing.start_span(
                "serving.admission_wait", parent=req.span.context,
                attrs={"queued_behind": len(self._queue)})
        self._queue.append(req)
        return rid

    def _slo_tenant(self) -> str:
        """Tenant name for per-tenant SLO attribution: the sched tenant
        when enrolled on a DeviceEngine, else the engine label."""
        t = self._sched_tenant
        return t.name if t is not None else self._engine_label

    def _shed_request(self, req: "_Request", why: str) -> None:
        """Deadline load shedding: finish the request EMPTY right now —
        spending prefill + decode on a result whose deadline has passed
        starves requests that can still meet theirs."""
        self._hc.count("shed")
        self._m_streams.labels(self._engine_label, "shed").inc()
        _rp.record_shed(
            "serving", f"{self._engine_label}: rid {req.rid} shed ({why})",
            engine=self._engine_label, rid=req.rid)
        shook = _slo.ENGINE_SLO_HOOK
        if shook is not None:
            shook.record_shed(
                self._slo_tenant(), "serving",
                wait_s=max(time.monotonic() - req.t_submit, 0.0))
        if req.wait_span is not None:
            req.wait_span.end()
        if req.span is not None:
            req.span.set_attribute("shed", True)
            req.span.end()
        req.done = True
        self._finished[req.rid] = req.out  # empty: the budget was spent

    def _reject(self, reason: str) -> None:
        """Flight-recorder entry for an admission rejection — one flag
        check while events are off."""
        self._hc.count("rejected")
        _events.record("serving.admission_reject",
                       f"{self._engine_label}: {reason}",
                       severity="warning", engine=self._engine_label,
                       reason=reason)

    def pending(self) -> int:
        """Requests not finished: queued, holding a slot, or past the
        slot they gave up with tokens still in a chunk not read."""
        return len(self._queue) + len(self._released) + sum(
            r is not None for r in self._slot_req)

    def progress(self, rid: int) -> Optional[List[int]]:
        """The tokens generated so far for request ``rid``, running or
        finished (a copy; empty while it is queued); None for an id this
        engine never handed out."""
        done = self._finished.get(rid)
        if done is not None:
            return list(done)
        running = self._running(rid)
        if running is not None:
            return list(running[1].out)
        return [] if any(r.rid == rid for r in self._queue) else None

    def slot_of(self, rid: int) -> Optional[int]:
        """The slot request ``rid`` occupies now, or None (queued,
        finished or unknown). A request whose last tokens are in a chunk
        not read yet has its slot until they are, even where the next
        request was admitted to it. The slot a finished request took is
        in the step record of the iteration that admitted it."""
        running = self._running(rid)
        return None if running is None else running[0]

    def _running(self, rid: int) -> Optional[Tuple[int, _Request]]:
        """(slot, request) of a request admitted and not finished."""
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                return slot, req
        return self._released.get(rid)

    def recent_steps(self) -> List[Dict[str, Any]]:
        """The records of the last ``STEP_RING`` iterations, oldest
        first (copies). A record: ``iteration`` (its ordinal, the value
        of ``stats["iterations"]``), ``start_ns`` (``monotonic_ns``),
        ``wall_s``, ``cpu_s`` (this thread's CPU time: wall far above
        it means the thread was blocked or descheduled, wall about equal
        means Python ran, a collection included), ``phases`` (seconds
        by phase name), ``admitted`` ([rid, slot, ahead] triples: ``ahead``
        where the slot's request before still had tokens in a chunk not
        read, ``stats["admits_ahead"]``), ``chunk``
        (decode steps dispatched, 0 for none), ``active`` and ``queued``
        (slots decoding, requests still waiting), ``lane_steps``,
        ``lane_rows`` and ``lane_tokens`` (those of the steps that carried
        prompt rows, the rows offered and the prompt tokens among them),
        ``gc`` (collections per generation that fell in it),
        ``first_use`` (whether an executable was used for the first
        time) and ``wait`` (its ``serving.device_wait`` phases split by
        cause: ``obs.tracing.wait_record``)."""
        with self._steps_lock:
            return [_copy_step(r) for r in self._steps]

    def slowest_steps(self) -> List[Dict[str, Any]]:
        """The ``STEP_KEEP_SLOWEST`` slowest iterations of the engine's
        life that were not first-use iterations, slowest first."""
        with self._steps_lock:
            return [_copy_step(r) for r in sorted(
                self._slow_steps, key=lambda r: -r["wall_s"])]

    def step_iteration(self) -> bool:
        """One scheduler iteration: admit into the slots that are free,
        then one decode chunk read (with prompts in the lane: a chunk that
        carries their windows). The contiguous engine with the lane runs a
        chunk ahead: it dispatches the next chunk before it reads the
        last, so the device has a program queued when one ends, and a
        prompt's first token is handed out with the tokens of the chunk
        behind it (``_decode``). Returns True while work remains; nothing
        is in flight once it returns False. When enrolled as a
        sched.DeviceEngine tenant, the iteration runs under the engine's
        deficit-round-robin fair share so serving steps and pipeline
        batches interleave on one chip, and dispatches nothing that
        outlasts the tenant's call."""
        tenant = self._sched_tenant
        if tenant is not None:
            ret = tenant.call(self._step_direct,
                              label=f"{self._engine_label}.step")
            # SHED only fires when the tenant carries a default
            # deadline; the iteration didn't run, so work remains
            return True if not isinstance(ret, bool) else ret
        return self._step_direct()

    def _step_direct(self, read_only: bool = False) -> bool:
        """An iteration; ``read_only`` is ``_drain``'s, which admits and
        dispatches nothing."""
        self._hc.beat()  # watchdog liveness: the scheduler is turning
        st = self.stats
        st["iterations"] += 1
        rec: Dict[str, Any] = {
            "iteration": st["iterations"], "admitted": [], "chunk": 0,
            "active": 0, "first_use": False,
            "lane_steps": 0, "lane_rows": 0, "lane_tokens": 0,
            "wait": _tracing.wait_record()}
        before = [st[key] for key in _PHASE_KEYS]
        gc0 = [g["collections"] for g in gc.get_stats()]
        cpu0 = time.thread_time_ns()
        with _tracing.phase(st, "serving.step") as step:
            step.set_attribute("iteration", rec["iteration"])
            if read_only:
                chunk, self._flight = self._flight, None
                self._read_chunk(chunk, step, rec)
            else:
                if self._kv_imports:  # truthiness: free when none arrived
                    self.drain_kv_imports()
                self._admit(step, rec)
                self._decode(step, rec)
        rec["cpu_s"] = (time.thread_time_ns() - cpu0) / 1e9
        rec["start_ns"] = step.start_ns
        rec["wall_s"] = step.seconds
        rec["phases"] = {name: st[key] - b for name, key, b
                         in zip(STEP_PHASES, _PHASE_KEYS, before)}
        rec["queued"] = len(self._queue)
        rec["gc"] = [g["collections"] - g0
                     for g, g0 in zip(gc.get_stats(), gc0)]
        self._keep_step(rec)
        return self.pending() > 0

    def _keep_step(self, rec: Dict[str, Any]) -> None:
        slow = self._slow_steps
        with self._steps_lock:
            self._steps.append(rec)
            if not rec["first_use"]:
                slow.append(rec)
                if len(slow) > self.STEP_KEEP_SLOWEST:
                    slow.remove(min(slow, key=lambda r: r["wall_s"]))
        if rec["wall_s"] > self.STEP_STALL_S and not rec["first_use"]:
            # the engine names its own stalled phase: which one stood
            # still, whether the thread ran (cpu_s), whether a collection
            # fell in it (gc, by generation), and what most of its waits
            # on the device were (wait)
            cause = _tracing.wait_cause(rec["wait"])
            log.warning("%s: iteration %d stood still for %.3f s "
                        "(device wait mostly %s): %s", self._engine_label,
                        rec["iteration"], rec["wall_s"], cause, rec)
            _events.record(
                "serving.step_stall",
                f"{self._engine_label}: iteration {rec['iteration']} "
                f"took {rec['wall_s']:.3f} s", severity="warning",
                engine=self._engine_label, cause=cause,
                step=_copy_step(rec))

    def _drain(self) -> None:
        """Read the chunk that is in flight, if one is, and retire what it
        ends, as an iteration of its own: called first by everything that
        looks at the device's state or at a request's from outside the
        loop, and by what changes how the loop runs."""
        if self._flight is not None:
            self._step_direct(read_only=True)

    # -- sched.DeviceEngine tenancy ---------------------------------------- #
    def enroll(self, scheduler: Any, *, name: Optional[str] = None,
               weight: float = 1.0, priority: int = 0) -> None:
        """Share the chip with streaming pipelines: register this engine
        as a tenant of a ``sched.DeviceEngine``. Subsequent
        ``step_iteration`` calls queue as opaque tenant work, so serving
        iterations and pipeline batches take turns under one
        deficit-round-robin fairness (docs/scheduler.md). Re-enrolling
        moves the engine to the new scheduler."""
        self.unenroll()
        self._sched_tenant = scheduler.register(
            name or self._engine_label, weight=weight, priority=priority)
        self._sched_engine = scheduler

    def unenroll(self) -> None:
        """Detach from the scheduler (no-op when not enrolled);
        step_iteration goes back to direct execution."""
        self._drain()
        tenant, eng = self._sched_tenant, self._sched_engine
        self._sched_tenant = None
        self._sched_engine = None
        if tenant is not None and eng is not None:
            eng.deregister(tenant)

    def run(self) -> Dict[int, List[int]]:
        """Drive until every queued/active request finishes; returns
        {request_id: generated tokens} for all finished requests."""
        while self.step_iteration():
            pass
        return dict(self._finished)

    @property
    def results(self) -> Dict[int, List[int]]:
        return dict(self._finished)

    @property
    def kv_stats(self) -> Optional[Dict[str, int]]:
        """Paged-KV-cache counters (hit/prompt tokens, COW copies,
        evictions, pages_peak, ...) or None when running contiguous."""
        self._drain()
        return None if self._kv is None else dict(self._kv.stats)

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        """Fraction of prompt tokens served from the radix prefix cache
        (0.0 before any lookup); None when running contiguous."""
        return None if self._kv is None else self._kv.prefix_hit_rate()

    # -- disaggregated serving (serving/disagg.py) ------------------------- #

    def kv_prefix_digest(self, max_entries: int = 64) -> List[str]:
        """Bounded radix-prefix digest for the fleet push doc — chained
        path hashes the router probes for prefix-aware placement.
        Empty when running contiguous."""
        return [] if self._kv is None else self._kv.prefix_digest(max_entries)

    def prefill_and_export(self, prompt: Sequence[int], *,
                           eos: Optional[int] = None,
                           temperature: float = 0.0, top_k: int = 0,
                           top_p: float = 1.0, seed: int = 0,
                           deadline: Any = None,
                           session: Optional[str] = None):
        """Prefill-role entry point: run chunked prefill over ``prompt``
        (max_new=1 — the one sampled token proves exactness), then
        export the finished full-page KV path for wire transfer.

        Returns ``(first_token_or_None, export_doc_or_None)``: the token
        is None when the request was shed (expired deadline) and the doc
        is None when no full page finished (short prompt) or the pages
        were evicted before export — the decode backend then simply
        re-prefills from scratch.
        """
        if self._kv is None:
            raise RuntimeError(
                "prefill_and_export requires the paged KV cache")
        self._drain()
        p = np.asarray(prompt, np.int32).reshape(-1)
        rid = self.submit(
            p, 1, eos, temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed, deadline=deadline, session=session)
        self.run()
        out = self._finished.get(rid, [])
        if not out:  # shed at the door or at admission
            return None, None
        return out[0], self._kv.export_pages(p)

    # -- live migration (fleet/migrate.py) --------------------------------- #

    def freeze_session(self, session: str) -> bool:
        """Refuse new submits for ``session`` while its KV pages are in
        flight to another backend. Returns whether the session has a
        recorded token path to export. In-flight requests already in a
        slot run to completion — freezing gates ADMISSION, not decode,
        so nothing in progress is torn."""
        self._drain()
        s = str(session)
        self._frozen_sessions.add(s)
        path = self._session_paths.get(s)
        if path is not None and s not in self._frozen_paths:
            # snapshot the path AT freeze time: retires replace (never
            # mutate) the recorded array, so holding this reference
            # pins exactly the state the freeze observed — the export
            # below ships it even if a slot retires mid-migration.
            # Re-freezing an already-frozen session keeps the ORIGINAL
            # snapshot (export_session freezes again before exporting;
            # it must not trade the pinned state for a racing retire's)
            self._frozen_paths[s] = path
        return s in self._frozen_paths

    def resume_session(self, session: str) -> None:
        """Lift a migration freeze (the absorb path when the page
        shipment failed and this backend must keep serving)."""
        s = str(session)
        self._frozen_sessions.discard(s)
        self._frozen_paths.pop(s, None)
        self._reprefill_sessions.add(s)

    def export_session(self, session: str) -> Optional[Dict[str, Any]]:
        """Freeze ``session`` and export the KV pages covering its last
        committed token path (``kv_cache.export_pages`` — the same doc
        the disagg prefill→decode hand-off ships). None when the engine
        runs contiguous, the session is unknown, or its pages were
        already evicted — the migration target then re-prefills.

        Freeze happens FIRST: a ``submit()`` racing this export gets
        the clean frozen-session error and fails over to the re-pinned
        target, and the exported doc covers the freeze-time path
        snapshot — never a half-updated one."""
        s = str(session)
        self.freeze_session(s)
        path = self._frozen_paths.get(s)
        if self._kv is None or path is None:
            return None
        return self._kv.export_pages(path)

    # -- crash checkpoint/restore (fleet/checkpoint.py) -------------------- #

    def session_watermarks(self) -> Dict[str, int]:
        """Committed token-path length per live session — the natural
        monotone checkpoint sequence number. Empty when no session has
        retired a turn yet."""
        return {s: int(p.size) for s, p in self._session_paths.items()}

    def checkpoint_session(
            self, session: str) -> Optional[Tuple[np.ndarray, Dict[str, Any]]]:
        """Read-only checkpoint snapshot: ``(token_path, pages_doc)``
        for the session's last committed turn, or None when the session
        is unknown, the engine runs contiguous, or the path's pages
        were already evicted. Unlike :meth:`export_session` this does
        NOT freeze — the session keeps serving; ``export_pages`` walks
        the radix tree read-only, so the daemon only ever sees a
        self-consistent (possibly one-turn-stale) path."""
        self._drain()
        path = self._session_paths.get(str(session))
        if path is None or self._kv is None:
            return None
        doc = self._kv.export_pages(path)
        if doc is None:
            return None
        return path, doc

    def adopt_restored_session(self, session: str, path: Any, *,
                               restored: bool = True) -> None:
        """Crash-restore adoption: record ``path`` as the session's
        committed token path (so the very next export/checkpoint works)
        and tag its next prefill for the diag critical path —
        ``restore`` when a fresh checkpoint's pages were spliced (the
        prefill rides the radix hit), ``re_prefill`` when the
        stale/corrupt/missing fallback recomputes from scratch."""
        s = str(session)
        if path is not None:
            seq = np.asarray(path, np.int32).reshape(-1)
            self._session_paths[s] = seq
            self._session_paths.move_to_end(s)
            while len(self._session_paths) > SESSION_PATHS_LIMIT:
                self._session_paths.popitem(last=False)
        self._frozen_sessions.discard(s)
        self._frozen_paths.pop(s, None)
        if restored:
            self._restored_sessions.add(s)
            self._reprefill_sessions.discard(s)
        else:
            self._reprefill_sessions.add(s)
            self._restored_sessions.discard(s)

    def enqueue_kv_import(self, doc: Dict[str, Any]) -> None:
        """Queue a wire-received page doc for splicing (any thread);
        the scheduler thread drains at the top of its next iteration."""
        with self._kv_imports_lock:
            self._kv_imports.append(doc)

    def drain_kv_imports(self) -> int:
        """Splice every queued page doc into the pool (scheduler thread
        or a quiesced engine only — PagedKVCache is single-threaded).
        Returns pages spliced; a rejected doc (geometry mismatch, pool
        exhaustion) is dropped with a flight-recorder event — the next
        request over that prefix just prefills locally."""
        if self._kv is None:
            return 0
        self._drain()
        spliced = 0
        while True:
            with self._kv_imports_lock:
                if not self._kv_imports:
                    break
                doc = self._kv_imports.popleft()
            try:
                spliced += self._kv.import_pages(doc)
            except (ValueError, RuntimeError) as e:
                _events.record(
                    "serving.kv_import_reject",
                    f"{self._engine_label}: page import dropped ({e})",
                    severity="warning", engine=self._engine_label)
        return spliced

    # -- scheduler internals ---------------------------------------------- #

    def _admit(self, step: "_tracing.phase", rec: Dict[str, Any]) -> None:
        """Take requests from the head of the queue into the free slots,
        lowest slot first. A request holds its slot (``_slot_req``) from
        here on, until it is retired or, on an engine that runs ahead,
        until the chunk that ends it by length is dispatched
        (``_release``): a slot that chunk frees is filled here, in the
        iteration after, and the new prompt's first window rides the chunk
        dispatched next, behind the one that ends the old request.

        On an engine with a prompt lane this is host work only: the
        slot's sampling controls are noted in the host's arrays, the slot
        is entered in ``_lane_at`` with nothing prefilled, and the chunks
        that follow carry the prompt through the lane, a window a step
        (``_plan_lane``); no device program is dispatched and nothing is
        read back, so no stream stands still for an admission. The other
        engines (paged, mesh-sharded, speculative) run their
        whole-prompt prefill program to its end here, and every stream
        stands still for it."""
        if not self._queue or all(r is not None for r in self._slot_req):
            return
        st = self.stats
        with _tracing.phase(st, "serving.admit", parent=step) as admit:
            for slot in range(self.n_slots):
                if self._slot_req[slot] is not None or not self._queue:
                    continue
                with _tracing.phase(st, "serving.admit_host",
                                    parent=admit) as host:
                    req = self._queue.popleft()
                    while req is not None and req.deadline is not None \
                            and req.deadline.expired():
                        # expired while queued: shed and give the slot to
                        # the next request that can still meet its deadline
                        self._shed_request(req, "deadline expired in queue")
                        req = self._queue.popleft() if self._queue else None
                    if req is None:
                        continue
                    plan = None
                    if self._kv is not None:
                        plan = self._paged_plan(req)
                        if plan is None:
                            # the pool cannot cover this request's page
                            # reservation yet: requeue at the FRONT (FIFO —
                            # no starvation by smaller latecomers) and stop
                            # admitting; pages free as active streams retire
                            self._queue.appendleft(req)
                            break
                    if req.wait_span is not None:
                        req.wait_span.end()
                    waited = host.start_ns / 1e9 - req.t_submit
                    st["admission_wait_s"] += waited
                    if waited > st["admission_wait_max_s"]:
                        st["admission_wait_max_s"] = waited
                    if self._lane:
                        self._admit_to_lane(slot, req, rec)
                        continue
                    t = int(req.prompt.size)
                    hit = self._paged_admit(slot, req, plan) \
                        if self._kv is not None else 0
                    ts = t - hit  # suffix tokens the prefill must compute
                    tb = self._bucket(t) if self._kv is None \
                        else min(self._bucket(ts), self._m_slot)
                    padded = np.zeros((1, tb), np.int32)
                    padded[0, :ts] = req.prompt[hit:]
                    skey = sampling.seed_key(req.seed)
                    temp = jnp.float32(req.temperature)
                    tk, tp = jnp.int32(req.top_k), jnp.float32(req.top_p)
                    sl = jnp.int32(slot)
                    # paged executables are distinct from contiguous ones
                    # (and the prefix-hit suffix prefill from the no-hit
                    # install), so they warm separate bucket entries /
                    # compile counters
                    bkey: Any = tb if self._kv is None \
                        else ("kv", hit > 0, tb)
                    blabel = str(tb) if self._kv is None or not hit \
                        else f"kv{tb}"
                    first_use = bkey not in self._seen_programs
                    cspan = _tracing.NOOP_SPAN
                    if req.span is not None and first_use:
                        # the jit call returns only after trace+compile
                        # on a new static shape; the dispatch itself is
                        # async, so ending right after _prefill_into
                        # bounds the compile
                        cspan = _tracing.start_span(
                            "serving.compile", parent=req.span.context,
                            attrs={"bucket": tb, "kernel": "prefill"})
                    pspan = self._prefill_span(req, slot, tb)
                    # obs/quality confidence tap: one None check selects
                    # the conf-variant prefill, which also returns the
                    # first-token logits' (entropy, top1, margin) for the
                    # retire path
                    want_conf = _quality.QUALITY_HOOK is not None
                # returns once the prefill is enqueued (after trace and
                # compile on a first use), not when the device has run it
                with _tracing.phase(st, "serving.prefill_dispatch",
                                    parent=admit) as pd:
                    if self._kv is None:
                        first = self._prefill_into(
                            slot, padded, t, skey, temp, tk, tp,
                            want_conf=want_conf)
                    else:
                        first = self._prefill_paged(
                            slot, padded, hit, ts, skey, temp, tk, tp,
                            want_conf=want_conf)
                if want_conf:
                    first, req.conf = first
                cspan.end()
                st["prefills"] += 1
                lbl = self._engine_label
                self._m_prefills.labels(lbl, blabel).inc()
                if first_use:
                    self._seen_programs.add(bkey)
                    self._m_compiles.labels(lbl, blabel).inc()
                    st["first_use_s"] += pd.seconds
                    rec["first_use"] = True
                self._m_streams.labels(lbl, "admitted").inc()
                with _tracing.phase(st, "serving.slot_insert", parent=admit):
                    self._tokens = _slot_insert(
                        self._tokens, first.reshape(1, 1), sl)
                    self._skeys = _slot_insert(self._skeys, skey, sl)
                    self._temp = _slot_insert(self._temp, temp, sl)
                    self._topk = _slot_insert(self._topk, tk, sl)
                    self._topp = _slot_insert(self._topp, tp, sl)
                # blocked on the device: the prefill and the inserts
                # finishing, then one D2H
                with _tracing.phase(st, "serving.first_token_wait",
                                    parent=admit) as fw:
                    with _tracing.phase(st, "serving.device_wait",
                                        parent=fw, awaited=(first,),
                                        record=rec["wait"]):
                        jax.block_until_ready(first)
                    with _tracing.phase(st, "serving.readback", parent=fw):
                        req.out.append(int(first))
                # TTFT after the int() materialization: the prefill
                # dispatch is async, so the first token only exists for
                # the caller once that D2H read completes
                self._m_ttft.observe(fw.end_ns / 1e9 - req.t_submit)
                pspan.end()  # prefill span covers through first-token D2H
                if _profile.ENGINE_HOOK is not None:
                    # the int(first) D2H above synced the prefill, so the
                    # interval is device-bound; first_use intervals are
                    # compile-dominated and recorded as such
                    _profile.ENGINE_HOOK.record_engine(
                        self, "prefill", pd.start_ns, fw.end_ns,
                        tokens=t, steps=1, compiled=first_use,
                        bucket=blabel, slot=slot)
                shook = _slo.ENGINE_SLO_HOOK
                if shook is not None:
                    shook.record_engine_phase(
                        self._slo_tenant(), "prefill",
                        (fw.end_ns - pd.start_ns) / 1e9)
                if req.span is not None:
                    req.decode_span = _tracing.start_span(
                        "serving.decode", parent=req.span.context,
                        attrs={"slot": slot})
                self._note_admission(slot, req, rec)
                self._pos_host[slot] = t
                self._slot_req[slot] = req
                self._retire_if_done(slot, req)

    def _prefill_span(self, req: "_Request", slot: int, bucket: int):
        """The ``serving.prefill`` span of a traced request, admission to
        first token, tagged where the prefill repeats or restores work."""
        if req.span is None:
            return _tracing.NOOP_SPAN
        pspan = _tracing.start_span(
            "serving.prefill", parent=req.span.context,
            attrs={"bucket": bucket, "slot": slot})
        if req.session is not None \
                and req.session in self._restored_sessions:
            # first prefill after a checkpoint splice — it rides the
            # imported radix pages; diag bills it as restore (cheap)
            # rather than re_prefill
            self._restored_sessions.discard(req.session)
            pspan.set_attribute("restore", True)
        elif req.session is not None \
                and req.session in self._reprefill_sessions:
            # post-absorb recompute, not fresh work — the diag critical
            # path bills this span as re_prefill
            self._reprefill_sessions.discard(req.session)
            pspan.set_attribute("re_prefill", True)
        return pspan

    def _admit_to_lane(self, slot: int, req: "_Request",
                       rec: Dict[str, Any]) -> None:
        """Give ``slot`` to ``req`` with nothing of its prompt prefilled:
        host work only. The slot's sampling controls go into the host's
        arrays (a slot that is not decoding yet does not use them)."""
        self._skeys[slot] = sampling.seed_key_host(req.seed)
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._lane_at[slot] = 0
        req.prefill_span = self._prefill_span(req, slot, LANE_ROWS)
        self.stats["prefills"] += 1
        lbl = self._engine_label
        self._m_prefills.labels(lbl, "lane").inc()
        self._m_streams.labels(lbl, "admitted").inc()
        self._note_admission(slot, req, rec)
        self._slot_req[slot] = req

    def _note_admission(self, slot: int, req: "_Request",
                        rec: Dict[str, Any]) -> None:
        """The step record's entry for ``req`` taking ``slot``, and
        whether it came ahead: the slot's request before still has tokens
        in a chunk not read."""
        ahead = any(s == slot for s, _ in self._released.values())
        self.stats["admits_ahead"] += ahead
        rec["admitted"].append([req.rid, slot, ahead])

    def _prefill_into(self, slot: int, padded, true_len: int, skey,
                      temp, tk, tp, want_conf: bool = False):
        """Prefill one padded prompt and install its cache into ``slot``;
        returns the first generated token (with the confidence triple
        appended when ``want_conf`` — the obs/quality admission path).
        The device-layout hook a mesh-sharded engine overrides
        (serving/tp_engine.py)."""
        conf = None
        if want_conf:
            first, kc, vc, pos, conf = _prefill_admit_conf(
                self.params, jnp.asarray(padded), jnp.int32(true_len),
                skey, temp, tk, tp,
                n_heads=self.n_heads, max_len=self.max_len)
        else:
            first, kc, vc, pos = _prefill_admit(
                self.params, jnp.asarray(padded), jnp.int32(true_len),
                skey, temp, tk, tp,
                n_heads=self.n_heads, max_len=self.max_len)
        sl = jnp.int32(slot)
        self._kc = _slot_insert(self._kc, kc, sl)
        self._vc = _slot_insert(self._vc, vc, sl)
        self._pos = _slot_insert(self._pos, pos, sl)
        return (first, conf) if want_conf else first

    # -- paged-KV scheduling ---------------------------------------------- #

    def _paged_plan(self, req: "_Request"):
        """Radix lookup + hit trimming + admissibility for one queued
        request. Returns the committed-to plan, or None while the pool
        cannot cover the request's page reservation."""
        kv = self._kv
        t = int(req.prompt.size)
        plan = kv.lookup(req.prompt)
        # the suffix prefills as a PADDED window at pos0 = hit, so the
        # hit plus the padded bucket width must fit the slot view; trim
        # the hit (COW tail first, then deepest node) until it does
        while plan.hit_len and plan.hit_len + min(
                self._bucket(t - plan.hit_len), self._m_slot) \
                > self._m_slot:
            plan.drop_tail()
        b_needed = -(-(t + req.max_new - 1) // kv.page_size)
        return plan if kv.admissible(plan, b_needed) else None

    def _paged_admit(self, slot: int, req: "_Request", plan) -> int:
        """Commit the plan — pin shared pages, COW-copy the partial
        match, allocate private prompt pages — and write the slot's
        page-table row. Returns the prefix-hit length in tokens (the
        suffix prefill starts there)."""
        kv = self._kv
        t = int(req.prompt.size)
        b_needed = -(-(t + req.max_new - 1) // kv.page_size)
        lease = kv.admit(plan, b_needed)
        req.kv_lease = lease
        row = np.zeros(self._kv_slot_pages, np.int32)
        row[:len(lease.pages)] = lease.pages
        self._table_host[slot] = row
        return lease.hit_len

    def _prefill_paged(self, slot: int, padded, hit: int, true_len: int,
                       skey, temp, tk, tp, want_conf: bool = False):
        """Prefill into the slot's pages: the no-hit path runs the
        UNCHANGED contiguous prefill at the slot-view capacity and
        scatters the result into pages (bit-identical by construction);
        a prefix hit prefills only the padded suffix window at pos0 =
        hit against the gathered view. ``want_conf`` selects the
        conf-variant kernels (obs/quality admission path) and switches
        the return to ``(first, conf)``."""
        kv = self._kv
        conf = None
        # copies of the host's page table go to the device, here and in
        # every dispatch: the host writes the table in place while a
        # program may still wait to run, and an array made from host
        # memory without a copy (``jnp.asarray`` on the CPU) reads it then
        table = jnp.array(self._table_host[slot])
        if hit == 0:
            if want_conf:
                first, kc, vc, pos, conf = _prefill_admit_conf(
                    self.params, jnp.asarray(padded), jnp.int32(true_len),
                    skey, temp, tk, tp,
                    n_heads=self.n_heads, max_len=self._m_slot)
            else:
                first, kc, vc, pos = _prefill_admit(
                    self.params, jnp.asarray(padded), jnp.int32(true_len),
                    skey, temp, tk, tp,
                    n_heads=self.n_heads, max_len=self._m_slot)
            kv.kpool, kv.vpool = _install_pages(
                kv.kpool, kv.vpool, kc, vc, table)
        elif want_conf:
            first, kv.kpool, kv.vpool, pos, conf = _prefill_paged_admit_conf(
                self.params, jnp.asarray(padded), kv.kpool, kv.vpool,
                table, jnp.int32(hit), jnp.int32(true_len),
                skey, temp, tk, tp, n_heads=self.n_heads)
        else:
            first, kv.kpool, kv.vpool, pos = _prefill_paged_admit(
                self.params, jnp.asarray(padded), kv.kpool, kv.vpool,
                table, jnp.int32(hit), jnp.int32(true_len),
                skey, temp, tk, tp, n_heads=self.n_heads)
        self._pos = _slot_insert(self._pos, pos, jnp.int32(slot))
        return (first, conf) if want_conf else first

    def _ensure_pages(self, active: List[int], w: int) -> None:
        """Grow active slots' page tables to cover the next ``w``
        write positions (capped at each request's reservation bound —
        writes past it route to the null page by table construction).
        Allocation cannot fail: admission reserved the full budget."""
        kv = self._kv
        ps = kv.page_size
        for s in active:
            req = self._slot_req[s]
            lease = req.kv_lease
            bound = int(req.prompt.size) + req.max_new - 1
            need = -(-min(self._pos_host[s] + w, bound) // ps)
            while len(lease.pages) < need:
                pid = kv.lease_alloc(lease)
                self._table_host[s, len(lease.pages) - 1] = pid

    def _runs_ahead(self) -> bool:
        """Whether an iteration dispatches the next chunk before it reads
        the last. The plan of a chunk needs lengths the host can count
        ahead and no token of the chunk before, so the order follows from
        what the engine is and whose unit of account the iteration is:
        the engines that prefill whole prompts block in every admission
        (which empties the device's queue anyway), and a chunk left
        running past a scheduler tenant's call would be charged to the
        next tenant. A speculative window is drafted from the last
        tokens and never runs ahead (``_decode``)."""
        return self._lane and self._sched_tenant is None

    def _decode(self, step: "_tracing.phase", rec: Dict[str, Any]) -> None:
        """Dispatch, read, retire, in the order this iteration takes.

        As it was on every engine, and still is where ``_runs_ahead`` says
        no: dispatch chunk k, read it, retire; behind the first token of
        the lane's last waiting prompt the next chunk runs in the same
        iteration. Running ahead: dispatch chunk k + 1, then read chunk k
        (dispatched an iteration earlier, or just now by an engine that
        was idle), then retire: the device has k + 1 queued when k ends,
        and the readback's tail and the host's work between two chunks
        leave the streams' time. An end by length the host counts at the
        dispatch of the chunk that holds it, and the slot is free for the
        next iteration's ``_admit`` from there (``_release``); while a
        queue waits, that chunk ends at the request's last token
        (``_dispatch_chunk`` states the rule). What the
        host cannot count ahead is an end the tokens decide (an EOS): the
        chunk ahead decodes such a request all the same, its columns are
        dropped when read (``_read_chunk``), and its slot is freed when
        the EOS is read. Whatever the order, nothing stays in flight once
        no request is left."""
        chunk = self._flight
        if chunk is None:
            if self.spec_draft > 0 and self._decode_speculative(step, rec):
                return
            chunk = self._dispatch_chunk(step, rec)
            if chunk is None:
                return
        ahead = self._runs_ahead()
        self._flight = self._dispatch_chunk(step, rec, ahead=True) \
            if ahead else None
        self._read_chunk(chunk, step, rec)
        if self._flight is not None and not self.pending():
            # the tokens just read ended the last request: nothing of the
            # chunk ahead is kept, and a quiet engine has nothing in flight
            chunk, self._flight = self._flight, None
            self._read_chunk(chunk, step, rec)
        elif not ahead and chunk.firsts and not self._lane_at:
            # the lane's last waiting prompt ended: the chunk behind its
            # first token runs in this iteration too, as the chunk behind
            # a whole-prompt prefill does
            chunk = self._dispatch_chunk(step, rec)
            if chunk is not None:
                self._read_chunk(chunk, step, rec)

    def _decoding(self) -> List[int]:
        """The slots that decode from the first step of the chunk about to
        be dispatched: those that hold a request whose prompt has left the
        lane. A request that a chunk in flight ends by length holds none
        (``_release``); every other engine has read each chunk it
        dispatched, and retired what it ended, before the next."""
        return [s for s, r in enumerate(self._slot_req)
                if r is not None and s not in self._lane_at]

    def _dispatch_chunk(self, step: "_tracing.phase", rec: Dict[str, Any],
                        ahead: bool = False) -> Optional[_Chunk]:
        """Plan the next chunk from what the host can count (positions,
        lengths, the lane's windows) and enqueue it; None where nobody
        decodes and no prompt waits. The host's mirrors (``_pos_host``,
        ``_lane_at``, a request's ``due``) advance here, so that the chunk
        behind this one can be planned before this one is read; the
        counters advance when it is read.

        A chunk runs ``chunk`` steps, fewer where a slot nears capacity,
        where what is left to decode is shorter, or, with a lane, where
        the prompts that wait have fewer windows. On an engine that runs
        ahead, while a request waits in the queue, a chunk also ends no
        later than the step in which the first decoding request ends by
        length: ``_release`` frees that slot at this dispatch, and the
        queue's head takes its first window in the chunk right behind,
        with no idle tail of this one in between (``chunks_cut`` counts
        the chunks this made shorter). Without a queue nobody waits for
        the slot, and an end by EOS is not known at the dispatch: neither
        cuts. A plain chunk shorter than ``chunk`` is floored to a power
        of two, and a lane chunk's count is a traced value, so the cut
        uses no executable the tails did not."""
        active = self._decoding()
        lane = bool(self._lane_at)
        if not active and not lane:
            return None
        # capacity headroom is PER-REQUEST capacity: max_len contiguous,
        # the kv_slot_pages * page_size view bound under paging;
        # page-pool headroom is NOT a gate: admission reserved every
        # active request's full page budget, so _ensure_pages below
        # always succeeds.
        # cap the chunk so no ACTIVE slot decodes past cache capacity
        # (an overflowing row NaN-poisons itself by contract); submit()'s
        # `prompt + max_new - 1 <= max_len` guard keeps cap >= 1 for
        # every active slot, so this never clamps to a forced overflow
        cap = self._m_slot - max(
            (self._pos_host[s] for s in active), default=0)
        if lane:
            # the lane sets the length: a lane chunk has a window in
            # every step and ends where the prompts that wait end. Its
            # step count is a traced value of ONE executable, whatever
            # the prompts' lengths and however many wait
            remaining = sum(-(-(int(self._slot_req[s].prompt.size) - at)
                              // LANE_ROWS)
                            for s, at in self._lane_at.items())
        else:
            remaining = max(self._left(self._slot_req[s]) for s in active)
        n = max(1, min(self.chunk, cap, remaining))
        uncut = self._floor_tail(n, lane)
        if active and self._queue and self._runs_ahead():
            n = max(1, min(n, min(self._left(self._slot_req[s])
                                  for s in active)))
        n = self._floor_tail(n, lane)
        if self._kv is not None:
            self._ensure_pages(active, n)
        st = self.stats
        key = ("lane", self.chunk) if lane else ("chunk", n)
        cspan = _tracing.NOOP_SPAN
        if lane and key not in self._seen_programs:
            # the lane's program on its first use: the request at the
            # lane's head waits for the trace and compile, as a request
            # waits for its bucket's on the whole-prompt engines
            head = self._slot_req[next(iter(self._lane_at))]
            if head.span is not None:
                cspan = _tracing.start_span(
                    "serving.compile", parent=head.span.context,
                    attrs={"bucket": LANE_ROWS, "kernel": "lane"})
        # the call returns once the chunk is enqueued (after trace and
        # compile on a first use), not when the device has run it
        with _tracing.phase(st, "serving.decode_dispatch",
                            parent=step) as dd:
            outs = self._run_chunk(n)
        cspan.end()
        self._note_first_use(key, dd, rec)
        rows = self._kv_rows_asked(active, n)
        for s in range(self.n_slots):
            self._pos_host[s] += n  # device pos advances for EVERY slot
        cols = {}
        for s in active:
            req = self._slot_req[s]
            keeps = min(n, self._left(req))
            req.due += keeps
            cols[s] = (req, 0, keeps)
        chunk = _Chunk(n, outs, lane, ahead, dd.start_ns, len(active),
                       slot_steps=n * len(active), kv_rows=rows,
                       counts=self._chunk_counts, cols=cols,
                       cut=n < uncut)
        if lane:
            self._join_lane(chunk)
        if self._runs_ahead():
            self._release(chunk)
        return chunk

    def _floor_tail(self, n: int, lane: bool) -> int:
        """A plain chunk's ``n`` steps, a TAIL floored to a power of two:
        chunk length is a static shape, so every distinct n is its own
        executable — pow2 tails bound the cache at log2(chunk) entries
        instead of one per tail length (full-size chunks keep the user's
        exact value, whatever it is). A lane chunk's count is traced."""
        if n < self.chunk and not lane:
            return 1 << (n.bit_length() - 1)
        return n

    def _release(self, chunk: _Chunk) -> None:
        """Free the slot of each request that ``chunk``, just dispatched,
        ends by length: the next iteration's ``_admit`` fills it, a chunk
        before the one that reads ``chunk``. The request is retired when
        ``chunk`` is read (``_read_chunk``, which finds it in ``cols``) and
        counts in ``pending`` until then (``_released``). On the device
        the new prompt's windows run after ``chunk``, since the stores are
        threaded from one chunk to the next, and write the slot's rows
        (and a recurrent state) from position 0 before anything reads
        them; ``chunk`` holds its own copies of the slot's controls."""
        for slot, (req, _, _) in chunk.cols.items():
            if self._left(req) == 0:
                self._released[req.rid] = (slot, req)
                self._free_slot(slot, req)

    def _note_first_use(self, key: Any, dispatch: "_tracing.phase",
                        rec: Dict[str, Any]) -> None:
        """The dispatch's wall as first use where this engine had not
        used that executable (``key``) before."""
        if key not in self._seen_programs:
            self._seen_programs.add(key)
            self.stats["first_use_s"] += dispatch.seconds
            rec["first_use"] = True

    @staticmethod
    def _left(req: _Request) -> int:
        """Tokens ``req`` has still to be given a step for."""
        return req.max_new - len(req.out) - len(req.held) - req.due

    def _join_lane(self, chunk: _Chunk) -> None:
        """Behind the dispatch of a chunk with a lane: note what the lane
        carried, and for each prompt that ends in it (its last window in
        step j) that its first token is ``outs[slot, j]`` and that it
        decodes the ``n - 1 - j`` steps behind, from its prompt's end. The
        slot leaves ``_lane_at``: in the next chunk it decodes."""
        plan, n = self._lane_plan, chunk.n
        chunk.lane_tokens = int(plan[:, 2].sum())
        chunk.conf = self._lane_conf
        for j in map(int, np.flatnonzero(plan[:, 3])):
            slot = int(plan[j, 0])
            req = self._slot_req[slot]
            del self._lane_at[slot]
            after = n - 1 - j
            self._pos_host[slot] = int(req.prompt.size)
            chunk.slot_steps += after
            chunk.kv_rows += self._kv_rows_asked([slot], after)
            self._pos_host[slot] += after
            keeps = min(after, req.max_new - 1)
            req.due += 1 + keeps
            chunk.firsts[slot] = j
            chunk.cols[slot] = (req, j + 1, keeps)

    def _read_chunk(self, chunk: _Chunk, step: "_tracing.phase",
                    rec: Dict[str, Any]) -> None:
        """Wait for ``chunk``'s tokens, advance the counters and the step
        record by it, hand the tokens out and retire what they end.

        A request that the chunk before this one ended (by a token: what
        ends by length was never planned into this one) has its columns
        dropped, and they count as waste: slots x steps = kept + wasted
        holds. The rows such a slot wrote lie past the request's own,
        where a later prompt's lane overwrites them in the device's
        order. A request this chunk ends by length gave its slot up when
        the chunk was dispatched (``_release``), and the slot may hold the
        next request by now: it is retired from ``cols`` all the same, and
        leaves the slot to its new holder. A first token is handed out
        (``out``, the TTFT, the end of ``prefill_span``) with the tokens
        of the chunk behind its own, so a stream's first token comes with
        its first chunk of tokens; a request that ends in the chunk of its
        first token gets all of them at once."""
        st = self.stats
        n = chunk.n
        # what the host reads of the chunk: its tokens, the routing counts
        # of a family that counts, the lane's confidences for a quality
        # hook (None where there is nothing to read)
        conf = chunk.conf if _quality.QUALITY_HOOK is not None else None
        need = (chunk.outs, chunk.counts, conf)
        behind = self._flight
        # the chunk behind tells a completion delivered late only where it
        # runs a full chunk's steps: a shorter one (a lane chunk whose
        # prompts end in a step or two, a tail) can be ready within the
        # witness's two looks by its own device time
        watched = None if behind is None or behind.n < self.chunk \
            else (behind.outs,)
        with _tracing.phase(st, "serving.decode_wait", parent=step) as dw:
            # the device running what is left of the chunk, its completion
            # delivered and this thread woken; the witness watches the
            # chunk and, on an engine that runs ahead, a full chunk behind
            with _tracing.phase(
                    st, "serving.device_wait", parent=dw, awaited=need,
                    behind=watched, record=rec["wait"]):
                jax.block_until_ready(need)
            # the copy of the ready arrays to the host: one round trip
            with _tracing.phase(st, "serving.readback", parent=dw):
                outs, counts, conf = jax.device_get(need)
        if behind is not None:
            # the device begins the chunk behind this one now
            behind.start_ns = dw.end_ns
        wall = (dw.end_ns - chunk.start_ns) / 1e9
        self._m_tok_lat.observe(wall / n)
        if _profile.ENGINE_HOOK is not None:
            # from when the device could begin the chunk (its dispatch, or
            # the end of the wait for the chunk it was queued behind) to
            # the end of its readback: its device time, the delivery of
            # its completion and the readback (device_wait, readback); the
            # occupancy sample drives the Perfetto serving counter lane
            _profile.ENGINE_HOOK.record_engine(
                self, "decode", chunk.start_ns, dw.end_ns,
                tokens=n * chunk.active, steps=n, active=chunk.active,
                queued=len(self._queue), slots=self.n_slots,
                # a prefill through the lane has no interval of its own
                lane_steps=n if chunk.lane else 0)
        shook = _slo.ENGINE_SLO_HOOK
        if shook is not None:
            shook.record_engine_phase(self._slo_tenant(), "decode", wall)
        # host bookkeeping with nothing on the device it waits for
        with _tracing.phase(st, "serving.retire", parent=step):
            rec["chunk"] += n
            rec["active"] = max(rec["active"], chunk.active)
            st["chunks"] += 1
            st["chunks_ahead"] += chunk.ahead
            st["chunks_cut"] += chunk.cut
            st["decode_steps"] += n
            st["slot_steps"] += chunk.slot_steps
            st["kv_rows_attended"] += chunk.kv_rows
            if counts is not None:
                fam = self._family
                for key, value in zip(fam.COUNTS, counts):
                    st[key] += int(value)
                rows = getattr(fam, "ROWS_ATTENDED", None)
                if rows:
                    st[rows] += chunk.kv_rows
            if chunk.lane:
                for key, value in (("lane_steps", n),
                                   ("lane_rows", n * LANE_ROWS),
                                   ("lane_tokens", chunk.lane_tokens)):
                    st[key] += value
                    rec[key] += value
            kept = 0
            for slot, (req, start, keeps) in chunk.cols.items():
                j = chunk.firsts.get(slot)
                new = [] if j is None else [int(outs[slot, j])]
                req.due -= len(new) + keeps
                if req.done:
                    st["ahead_dropped_slot_steps"] += n - start
                    continue
                if j is not None and conf is not None:
                    req.conf = conf[j]
                # the columns past `keeps` count as waste, and so do those
                # behind a token that ends the request
                for tok in map(int, outs[slot, start:start + keeps]):
                    if new and new[-1] == req.eos:
                        break
                    new.append(tok)
                    kept += 1
                ends = new[-1] == req.eos or \
                    len(req.out) + len(req.held) + len(new) >= req.max_new
                if j is not None and not ends:
                    req.held = new
                    continue
                if req.prefill_span is not None:
                    self._first_token_out(slot, req, dw.end_ns)
                req.out += req.held + new
                req.held = []
                self._retire_if_done(slot, req)
            # invariant: slots x steps = kept tokens + wasted: wasted
            # are the steps of empty and still-prefilling slots, those
            # past a request's end and those dropped
            st["wasted_slot_steps"] += n * self.n_slots - kept

    def _first_token_out(self, slot: int, req: _Request,
                         end_ns: int) -> None:
        """A prompt prefilled through the lane gets its first token: the
        TTFT, the end of its prefill span, the start of its decode span."""
        self._m_ttft.observe(end_ns / 1e9 - req.t_submit)
        req.prefill_span.end()
        req.prefill_span = None
        if req.span is not None:
            req.decode_span = _tracing.start_span(
                "serving.decode", parent=req.span.context,
                attrs={"slot": slot})

    def _plan_lane(self, n: int) -> np.ndarray:
        """The lane's rows for the ``n`` steps of the chunk about to run,
        (chunk, 4 + LANE_ROWS) int32 as ``_chunk_scan`` reads them (rows
        past ``n`` are not run): the prompts that wait in ``_lane_at``
        follow each other in the order they were admitted, a window of
        LANE_ROWS tokens a step, aligned to that width from the prompt's
        start. Advances ``_lane_at``; ``_dispatch_chunk`` sizes ``n`` so
        that every step has a window."""
        plan = np.zeros((self.chunk, 4 + LANE_ROWS), np.int32)
        step = 0
        for slot, at in self._lane_at.items():
            prompt = self._slot_req[slot].prompt
            t = int(prompt.size)
            while at < t and step < n:
                count = min(LANE_ROWS, t - at)
                plan[step, :4] = (slot, at, count, at + count == t)
                plan[step, 4:4 + count] = prompt[at:at + count]
                at += count
                step += 1
            self._lane_at[slot] = at
            if step == n:
                break
        return plan

    def _run_chunk(self, n: int):
        """Run ``n`` decode steps over all slots, updating the carried
        device state; returns the (S, n) generated tokens ((S, chunk), of
        which the first ``n`` columns count, where the steps carried the
        prompt lane). The second
        device-layout hook a mesh-sharded engine overrides (the paged
        branch never reaches a TP engine — it pins kv_page_size=0)."""
        # the slots that decode: the others are not attended
        active = np.zeros(self.n_slots, bool)
        active[self._decoding()] = True
        if self._kv is not None:
            kv = self._kv
            (self._tokens, kv.kpool, kv.vpool, self._pos, outs) = \
                _decode_chunk_paged(
                    self.params, self._tokens, kv.kpool, kv.vpool,
                    jnp.array(self._table_host), self._pos, active,
                    self._skeys, self._temp, self._topk, self._topp,
                    n_heads=self.n_heads, n_steps=n)
            return outs
        # with prompts waiting, the chunk's steps each carry a window
        lane = None
        if self._lane_at:
            self._lane_plan = self._plan_lane(n)
            lane = (self._lane_plan, np.int32(n))
        # the lane engine's controls are host arrays that admission and
        # retirement write in place while this chunk may still wait for
        # the one before it: it gets copies
        controls = [np.array(a) if self._lane else a for a in
                    (self._skeys, self._temp, self._topk, self._topp)]
        (self._tokens, self._kc, self._vc, self._pos, outs,
         self._lane_conf, *counts) = _decode_chunk(
            self.params, self._tokens, self._kc, self._vc, self._pos,
            active, *controls, lane,
            n_heads=self.n_heads, n_steps=self.chunk if lane else n)
        self._chunk_counts = counts[0] if counts else None
        return outs

    def _kv_rows_asked(self, active: List[int], n: int) -> int:
        """K/V rows of the store the ``n`` steps of a chunk are asked to
        read: each active slot's position at each step, rounded up to the
        grain of the attention kernel the block runs. A mesh-sharded
        engine, whose body reads the whole store, overrides it."""
        return sum(decode_attention.rows_read(
            self._pos_host[s] + j, self._m_slot, self._kv_grain)
            for s in active for j in range(n))

    def _run_verify(self, tokens_in):
        """Device kernel hook for one speculative verify iteration —
        the TP engine swaps in its mesh-sharded verify chunk."""
        if self._kv is not None:
            kv = self._kv
            carried, kv.kpool, kv.vpool, pos, outs, m = \
                _verify_chunk_paged(
                    self.params, tokens_in, kv.kpool, kv.vpool,
                    jnp.array(self._table_host), self._pos,
                    n_heads=self.n_heads)
            return carried, self._kc, self._vc, pos, outs, m
        return _verify_chunk(self.params, tokens_in, self._kc, self._vc,
                             self._pos, n_heads=self.n_heads)

    def _decode_speculative(self, step: "_tracing.phase",
                            rec: Dict[str, Any]) -> bool:
        """One speculative iteration, where it pays: host-drafted
        prompt-lookup tokens verified in one dispatch; per-slot acceptance
        rolls pos back past rejected drafts (lm_verify_window's
        overwrite-before-visible invariant makes that roll-back free).
        False where the iteration is a plain chunk's: near capacity, with
        a sampled stream, or with one token left to make. The drafts are
        made from the last tokens, so a window is dispatched and read in
        one iteration, whatever the order of the plain chunks."""
        active = self._decoding()
        g = self.spec_draft
        # verify writes g + 1 cache slots per iteration; near capacity
        # (PER-REQUEST capacity: a bounded paged view would NaN-poison)
        # fall through to plain chunks, which self-cap. Speculation is
        # gated to ALL-greedy active sets: a sampled stream can only
        # accept one token per dispatch (its draw is sequential by
        # definition), so any batch containing one is served strictly
        # better by chunked decode. The last gate: a verify window costs
        # (g + 1)x a decode step's matmul rows — pointless when every
        # active stream needs at most one more token (the chunk path caps
        # its step count by `remaining` instead)
        if not active or self._m_slot - max(
                self._pos_host[s] for s in active) < g + 1 \
                or any(self._slot_req[s].temperature > 0.0 for s in active) \
                or all(self._left(self._slot_req[s]) <= 1 for s in active):
            return False
        if self._kv is not None:
            self._ensure_pages(active, g + 1)
        rec["active"] = max(rec["active"], len(active))
        drafts = np.zeros((self.n_slots, g), np.int32)
        for s in active:
            drafts[s] = self._draft_tokens(self._slot_req[s], g)
        st = self.stats
        rec["chunk"] = g + 1
        with _tracing.phase(st, "serving.decode_dispatch",
                            parent=step) as dd:
            tokens_in = jnp.concatenate(
                [self._tokens[:, 0], jnp.asarray(drafts)],
                axis=1)  # (S, 1+g)
            (self._tokens, self._kc, self._vc, self._pos, outs, m) = \
                self._run_verify(tokens_in)
        with _tracing.phase(st, "serving.decode_wait", parent=step) as dw:
            with _tracing.phase(st, "serving.device_wait", parent=dw,
                                awaited=(outs, m), record=rec["wait"]):
                jax.block_until_ready((outs, m))
            with _tracing.phase(st, "serving.readback", parent=dw):
                outs, m = jax.device_get((outs, m))
        st["chunks"] += 1
        self._note_first_use(("verify", g + 1), dd, rec)
        wall = (dw.end_ns - dd.start_ns) / 1e9
        # per-token latency of the verify dispatch: wall over the mean
        # ACCEPTED tokens across active slots (that is what a consumer
        # of this stream experienced)
        accepted = float(np.mean(m[active])) if active else 1.0
        self._m_tok_lat.observe(wall / max(accepted, 1.0))
        if _profile.ENGINE_HOOK is not None:
            _profile.ENGINE_HOOK.record_engine(
                self, "verify", dd.start_ns, dw.end_ns,
                tokens=int(np.sum(m[active])) if active else 0, steps=1,
                active=len(active), queued=len(self._queue),
                slots=self.n_slots, draft=g)
        shook = _slo.ENGINE_SLO_HOOK
        if shook is not None:
            shook.record_engine_phase(self._slo_tenant(), "verify", wall)
        with _tracing.phase(st, "serving.retire", parent=step):
            for s in range(self.n_slots):
                # unlike chunks, per-slot advance is data-dependent — the
                # mirror updates from the fetched acceptance counts
                self._pos_host[s] += int(m[s])
            st["spec_iterations"] += 1
            for slot in active:
                req = self._slot_req[slot]
                took = 0
                for i in range(int(m[slot])):
                    if req.done or len(req.out) >= req.max_new:
                        break
                    tok = int(outs[slot, i])
                    req.out.append(tok)
                    took += 1
                    if req.eos is not None and tok == req.eos:
                        req.done = True
                st["spec_drafted"] += g
                # tokens beyond the first are the speculation win: they
                # would each have cost a dispatch under chunk=1 decode
                st["spec_accepted"] += max(0, took - 1)
                self._retire_if_done(slot, req)
        if _tune.TUNE_HOOK is not None:
            self._retune_spec_draft()
        return True

    #: re-derive the draft length every this many verify iterations —
    #: often enough to track workload shifts, rare enough to cost nothing
    _SPEC_RETUNE_EVERY = 32
    #: per-dispatch overhead expressed in verify-row equivalents: the
    #: fixed cost a verify window amortizes (scheduler step + dispatch
    #: + D2H fetch). Small models in this codebase are overhead-bound,
    #: so the constant is deliberately generous; it only shapes WHERE
    #: the accept-rate curve peaks, not whether speculation runs.
    _SPEC_OVERHEAD_ROWS = 4.0

    def _retune_spec_draft(self) -> None:
        """Pick the draft length whose EXPECTED tokens per verify cost
        is highest under the observed per-token accept rate. Expected
        tokens for draft k is the geometric partial sum
        1 + a + ... + a^k; cost is the
        (k+1)-row verify window plus fixed dispatch overhead. Closed
        form — no sweep, and only reached when speculation is already
        on (spec_draft > 0 gates _decode)."""
        it = self.stats["spec_iterations"]
        if self.spec_draft <= 0 or it == 0 \
                or it % self._SPEC_RETUNE_EVERY:
            return
        drafted = self.stats["spec_drafted"]
        if drafted < self._SPEC_RETUNE_EVERY:
            return
        a = min(max(self.stats["spec_accepted"] / drafted, 0.0), 0.99)
        cap = min(16, max(self._m_slot - 1, 1))
        best_k, best_rate = 1, 0.0
        for k in range(1, cap + 1):
            toks = (1.0 - a ** (k + 1)) / (1.0 - a)
            rate = toks / (self._SPEC_OVERHEAD_ROWS + k + 1)
            if rate > best_rate + 1e-9:
                best_k, best_rate = k, rate
        if best_k != self.spec_draft:
            tn = _tune.TUNE_HOOK
            if tn is not None:
                tn.observe(
                    "lm_spec_draft", _tune.device_kind(), "serving.lm",
                    _tune.shape_sig(("len", self.max_len)), best_k)
            self.spec_draft = best_k

    @staticmethod
    def _draft_tokens(req: _Request, g: int) -> np.ndarray:
        """Prompt-lookup drafting: find the last earlier occurrence of
        the stream's trailing n-gram (n=3,2,1) in its own history and
        propose the g tokens that followed it (padded by repetition).
        Model-free — correctness never depends on draft quality, only
        the acceptance rate does."""
        hist = np.concatenate(
            [req.prompt, np.asarray(req.out, np.int32)])
        for n in (3, 2, 1):
            if len(hist) <= n:
                continue
            pat = hist[-n:]
            windows = np.lib.stride_tricks.sliding_window_view(
                hist[:-1], n)
            hits = np.flatnonzero((windows == pat).all(1))
            if len(hits):
                i = int(hits[-1])
                cont = hist[i + n:i + n + g]
                out = np.full(g, int(cont[-1]), np.int32)
                out[:len(cont)] = cont
                return out
        return np.full(g, int(hist[-1]), np.int32)

    def _retire_if_done(self, slot: int, req: _Request) -> None:
        # both append sites stop at an eos token immediately, so eos can
        # only ever be the LAST element — no truncation needed
        hit_eos = req.eos is not None and bool(req.out) \
            and req.out[-1] == req.eos
        if hit_eos or len(req.out) >= req.max_new:
            req.done = True
            if req.decode_span is not None:
                # tokens-per-decode-span: with the span duration this
                # yields the request's realized per-token decode latency
                req.decode_span.set_attribute("tokens", len(req.out) - 1)
                req.decode_span.end()
            if req.span is not None:
                req.span.set_attribute("tokens", len(req.out))
                req.span.end()
            self.stats["tokens_out"] += len(req.out)
            self._m_streams.labels(self._engine_label, "completed").inc()
            self._m_tokens.inc(len(req.out))
            shook = _slo.ENGINE_SLO_HOOK
            if shook is not None:
                missed = (req.deadline is not None
                          and req.deadline.expired())
                shook.record_outcome(
                    self._slo_tenant(), "missed" if missed else "met",
                    max(time.monotonic() - req.t_submit, 0.0))
            dhook = _diag.DIAG_HOOK
            if dhook is not None:
                dhook.observe_request(
                    self._engine_label, req.rid, req.session,
                    req.span.context.trace_id
                    if req.span is not None else None,
                    max(time.monotonic() - req.t_submit, 0.0))
            qhook = _quality.QUALITY_HOOK
            if qhook is not None and req.conf is not None:
                # materialize the (3,) confidence triple the admission
                # prefill computed on-device; quality-off runs never
                # allocate it, so this D2H read costs nothing then
                ent, top1, margin = np.asarray(req.conf, np.float64)
                qhook.record_confidence(
                    self._engine_label, self._slo_tenant(), req.session,
                    float(ent), float(top1), float(margin))
            self._finished[req.rid] = req.out
            if self._released.pop(req.rid, None) is None:
                self._free_slot(slot, req)
            if self._kv is not None and req.kv_lease is not None:
                # positions 0..consumed-1 hold valid K/V (the final
                # output token was never written back); register those
                # full pages as shareable prefix nodes, free the rest
                seq = req.prompt if len(req.out) <= 1 else np.concatenate(
                    [req.prompt, np.asarray(req.out[:-1], np.int32)])
                self._kv.release(req.kv_lease, seq)
                req.kv_lease = None
                if req.session is not None:
                    # the committed token path IS the session's
                    # exportable KV state — fleet/migrate.py ships the
                    # pages covering it on a scale-in drain
                    self._session_paths[req.session] = seq
                    self._session_paths.move_to_end(req.session)
                    while len(self._session_paths) > SESSION_PATHS_LIMIT:
                        self._session_paths.popitem(last=False)
                if self._slot_req[slot] is None:
                    # else the row is the next request's: a release
                    # (``_release``) let it take the slot before this read
                    self._table_host[slot] = 0

    def _free_slot(self, slot: int, req: _Request) -> None:
        """``req`` gives ``slot`` up: retired, or its last chunk
        dispatched (``_release``), which holds its own copies of the
        slot's controls."""
        self._slot_req[slot] = None
        if req.temperature > 0.0 and self._lane:
            # restore greedy defaults so a finished sampled stream
            # doesn't keep the all-greedy fast path disabled for the
            # slots that remain
            self._temp[slot], self._topk[slot] = 0.0, 0
            self._topp[slot] = 1.0
        elif req.temperature > 0.0:
            # the same on the device (and for the speculation gate)
            sl = jnp.int32(slot)
            self._temp = _slot_insert(self._temp, jnp.float32(0.0), sl)
            self._topk = _slot_insert(self._topk, jnp.int32(0), sl)
            self._topp = _slot_insert(self._topp, jnp.float32(1.0), sl)
