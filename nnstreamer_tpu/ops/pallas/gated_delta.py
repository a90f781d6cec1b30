"""Gated DeltaNet's recurrent state, dense and as a Pallas TPU kernel.

A Gated DeltaNet layer (arXiv:2412.06464; Qwen3-Next's linear-attention
layers) keeps for each stream and value head h a (dk, dv) state S in place
of a store of rows. One token, with its key k and query q of key head
``h // (H / Hk)`` (L2-normalised, q scaled by ``dk ** -0.5``), its value v,
its gate g < 0 and its step size beta in (0, 1), updates and reads it by
the gated delta rule:

    S <- exp(g) S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

``gated_delta_step`` is the decode step's: one token a stream, the state
updated in place. On a TPU the kernel's work list has one item a stream
that holds a request, and an item reads the stream's H states of the layer
once (one DMA of H x dk x dv floats into a buffer of three, so that the
next item's read and the last one's write-back run under this one's
arithmetic), updates them in VMEM and writes them back once, through HBM
refs aliased in and out. A stream that holds no request has no item, so
its state is neither read nor changed. Arithmetic is float32 on the VPU:
the products are one row against a (dk, dv) state, which the MXU would run
at one row of 128, and the VPU keeps the precision the configuration
states with no bf16 passes. A key or query enters as a row; its column
(for ``k u^T`` and for the sums over dk) is the transpose of the row
broadcast to (dv, dk).

``lane_window`` is the prompt lane's: a window of P rows of ONE stream,
from the stream's state (or from zero where the window is the prompt's
first), in the chunked form of the same rule (``window_chunked``: the
window's updates as one lower-triangular solve and a few products), and
the state after its last real row written back. ``window_sequential`` is
the rule row by row, which the tests hold the chunked form to.

Where the computation is not placed on a TPU the dense form runs
(``step_reference``, ``ops/pallas.per_platform``); it is also what the tests
hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import per_platform

_HI = jax.lax.Precision.HIGHEST
#: buffers of one item's states: the item computed, the next one's read
#: and the last one's write-back in flight
_BUFFERS = 3


def _repeat(x, n_heads: int):
    """(.., Hk, d) rows of the key heads as rows of the H value heads."""
    return jnp.repeat(x, n_heads // x.shape[-2], axis=-2)


def step_reference(q, k, v, decay, beta, state, li, active=None):
    """Dense form of one decode token a stream.

    q, k: (B, Hk, dk); v: (B, H, dv); decay (``exp(g)``), beta: (B, H);
    state: the store ``(B, L, H, dk, dv)``; li: the layer; active: (B,)
    bool or None. A stream that is not active keeps its state and its
    output is zero. Returns (o (B, H, dv), state)."""
    h = v.shape[1]
    s = jax.lax.dynamic_index_in_dim(state, li, 1, keepdims=False)
    kk, qq = _repeat(k, h), _repeat(q, h)
    s = s * decay[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", kk, s,
                                          precision=_HI))
    s = s + kk[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", qq, s, precision=_HI)
    if active is not None:
        old = jax.lax.dynamic_index_in_dim(state, li, 1, keepdims=False)
        s = jnp.where(active[:, None, None, None], s, old)
        o = jnp.where(active[:, None, None], o, 0.0)
    return o, jax.lax.dynamic_update_index_in_dim(state, s, li, 1)


def _step_kernel(li_ref, n_ref, item_b_ref, decay_ref, beta_ref, q_ref,
                 k_ref, v_ref, st_hbm, o_ref, st_out, buf, rsem, wsem, *,
                 n_heads: int, n_key_heads: int, n_layers: int):
    del st_hbm  # the same buffer as st_out (aliased)
    n = n_ref[0]
    rep = n_heads // n_key_heads
    dv = buf.shape[-1]

    def row(t):
        return item_b_ref[t] * n_layers + li_ref[0]

    def fetch(t, slot):
        return pltpu.make_async_copy(st_out.at[row(t)], buf.at[slot],
                                     rsem.at[slot])

    def store(t, slot):
        return pltpu.make_async_copy(buf.at[slot], st_out.at[row(t)],
                                     wsem.at[slot])

    # a stream without an item (no request) gets zeros
    o_ref[...] = jnp.zeros_like(o_ref)
    pl.when(n > 0)(lambda: fetch(0, 0).start())

    def pick(rows, i):
        """Row i of a (R, d) value, as (1, d)."""
        at = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == i
        return jnp.sum(jnp.where(at, rows, 0.0), axis=0, keepdims=True)

    def column(row, lanes):
        """(1, d) row as a (d, lanes) array whose column holds it."""
        return jnp.transpose(jnp.broadcast_to(row, (lanes, row.shape[1])))

    def item(t, carry):
        slot = t % _BUFFERS
        nxt = (t + 1) % _BUFFERS

        @pl.when(t + 1 < n)
        def _prefetch():
            # the buffer the next item reads into was written back from
            # ``_BUFFERS - 1`` items ago
            pl.when(t + 1 >= _BUFFERS)(
                lambda: store(t + 1 - _BUFFERS, nxt).wait())
            fetch(t + 1, nxt).start()

        fetch(t, slot).wait()
        b = item_b_ref[t]
        qb = q_ref[b].astype(jnp.float32)               # (Hk, dk)
        kb = k_ref[b].astype(jnp.float32)
        vb = v_ref[b].astype(jnp.float32)               # (H, dv)

        def key_head(j, out):
            qc = column(pick(qb, j), dv)                # (dk, dv): q_i
            kc = column(pick(kb, j), dv)
            for r in range(rep):
                h = j * rep + r
                s = buf[slot, h] * decay_ref[b * n_heads + h]
                mem = jnp.sum(s * kc, axis=0, keepdims=True)     # S^T k
                u = beta_ref[b * n_heads + h] * (pick(vb, h) - mem)
                s = s + kc * u
                buf[slot, h] = s
                o = jnp.sum(s * qc, axis=0, keepdims=True)       # S^T q
                at = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) == h
                out = jnp.where(at, o, out)
            return out

        o_ref[b] = jax.lax.fori_loop(
            0, n_key_heads, key_head,
            jnp.zeros((n_heads, dv), jnp.float32)).astype(o_ref.dtype)
        store(t, slot).start()
        return carry

    jax.lax.fori_loop(0, n, item, 0)
    for j in range(_BUFFERS):
        # the write-backs still in flight: the last min(n, _BUFFERS) items'
        last = n - 1 - j
        pl.when(last >= 0)(
            lambda last=last: store(last, last % _BUFFERS).wait())


def _step_pallas(q, k, v, decay, beta, state, li, active=None, *,
                 interpret: bool):
    b, hk, dk = q.shape
    h, dv = v.shape[1:]
    n_layers = state.shape[1]
    if active is None:
        active = jnp.ones((b,), bool)
    # the work list: the active streams in order
    n = jnp.sum(active).astype(jnp.int32)
    item_b = jnp.argsort(~active, stable=True).astype(jnp.int32)
    flat = (b * n_layers, h, dk, dv)
    kernel = functools.partial(_step_kernel, n_heads=h, n_key_heads=hk,
                               n_layers=n_layers)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, st = pl.pallas_call(
        kernel,
        in_specs=[smem] * 5 + [vmem] * 3 + [hbm],
        out_specs=[vmem, hbm],
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(flat, state.dtype)],
        scratch_shapes=[pltpu.VMEM((_BUFFERS, h, dk, dv), state.dtype),
                        pltpu.SemaphoreType.DMA((_BUFFERS,)),
                        pltpu.SemaphoreType.DMA((_BUFFERS,))],
        input_output_aliases={8: 1},
        # the state buffers, the rows in and out, and the temporaries
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BUFFERS * h * dk * dv * 4
            + 4 * b * h * dv * 4 + (16 << 20)),
        name="gated_delta",
        interpret=interpret,
    )(jnp.asarray(li, jnp.int32).reshape(1), n.reshape(1), item_b,
      decay.reshape(-1).astype(jnp.float32),
      beta.reshape(-1).astype(jnp.float32), q, k, v, state.reshape(flat))
    return o, st.reshape(state.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step(q, k, v, decay, beta, state, li, active=None, *,
                     interpret: bool = False):
    """One layer's recurrent update of a one-token decode step, the state
    updated in place. Arguments and results as :func:`step_reference`. On
    a TPU the kernel runs; elsewhere, and where a head's key or value
    width is not a multiple of the 128 lanes or the state is not float32,
    the dense form. ``interpret=True`` (tests) runs the kernel through the
    Pallas interpreter. Jitted, so that the unrolled layers of a decode
    step trace and lower the kernel once."""
    dk, dv = state.shape[-2:]
    args = (q, k, v, decay, beta, state, li)
    if active is not None:
        args += (active,)
    if (dk % 128 or dv % 128 or state.dtype != jnp.float32) \
            and not interpret:
        return step_reference(*args)
    kernel = functools.partial(_step_pallas, interpret=interpret)
    return per_platform(kernel, step_reference, interpret, *args)


# --------------------------------------------------------------------------- #
# a window of rows of one stream
# --------------------------------------------------------------------------- #

def window_sequential(q, k, v, g, beta, s0):
    """The rule row by row over a window of one stream. q, k: (P, Hk, dk);
    v: (P, H, dv); g, beta: (P, H); s0: (H, dk, dv). Returns (o (P, H, dv),
    the state after the last row)."""
    h = v.shape[1]

    def one(s, row):
        qt, kt, vt, gt, bt = row
        s = s * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, s,
                                           precision=_HI))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=_HI)

    s, o = jax.lax.scan(one, s0, (_repeat(q, h), _repeat(k, h), v, g, beta))
    return o, s


def window_chunked(q, k, v, g, beta, s0):
    """The same rule over the window at once (the chunked form of the Gated
    DeltaNet paper). With ``G`` the gates' running sum, row t's update
    ``u_t = beta_t (v_t - e^{G_t} S0^T k_t - sum_{s<t} e^{G_t - G_s}
    (k_t . k_s) u_s)`` is a unit lower-triangular system in the window's
    ``u``; the outputs and the final state are products with ``u`` and
    ``S0``. Arguments and results as :func:`window_sequential`."""
    p, h = g.shape
    dot = functools.partial(jnp.einsum, precision=_HI)
    qh = _repeat(q, h).transpose(1, 0, 2)                   # (H, P, dk)
    kh = _repeat(k, h).transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)                               # (H, P, dv)
    big_g = jnp.cumsum(g, axis=0).T                         # (H, P)
    bh = beta.T
    lower = jnp.tril(jnp.ones((p, p), bool))
    decay = jnp.exp(jnp.where(
        lower, big_g[:, :, None] - big_g[:, None, :], -jnp.inf))
    strict = decay * (1.0 - jnp.eye(p, dtype=decay.dtype))
    system = jnp.eye(p, dtype=decay.dtype) \
        + bh[:, :, None] * dot("htd,hsd->hts", kh, kh) * strict
    rhs = bh[:, :, None] * (
        vh - jnp.exp(big_g)[:, :, None] * dot("htd,hdv->htv", kh, s0))
    u = jax.lax.linalg.triangular_solve(system, rhs, left_side=True,
                                        lower=True, unit_diagonal=True)
    o = jnp.exp(big_g)[:, :, None] * dot("htd,hdv->htv", qh, s0) \
        + dot("hts,hsv->htv", dot("htd,hsd->hts", qh, kh) * decay, u)
    last = big_g[:, -1]
    s = jnp.exp(last)[:, None, None] * s0 + dot(
        "hsd,hsv->hdv", kh * jnp.exp(last[:, None] - big_g)[:, :, None], u)
    return o.transpose(1, 0, 2), s


def lane_window(q, k, v, g, beta, state, li, slot, pos0, count):
    """One layer's rule over a window of P prompt rows of ONE stream, with
    the store updated: the rows ``pos0 .. pos0 + count - 1`` are real, the
    rest padding; the window starts from ``state[slot, li]``, or from zero
    where ``pos0`` is 0 (a new request's state is never read), and the
    state after its last real row is written back. Padding rows are given
    no step and no decay, so they leave the state as they find it.
    Arguments as :func:`window_sequential`, ``state`` the store ``(B, L, H,
    dk, dv)``. Returns (o (P, H, dv), state)."""
    p = g.shape[0]
    real = (jnp.arange(p) < count)[:, None]
    s0 = jax.lax.dynamic_slice(
        state, (slot, li, 0, 0, 0), (1, 1) + state.shape[2:])[0, 0]
    s0 = jnp.where(pos0 == 0, 0.0, s0)
    o, s = window_chunked(q, k, v, jnp.where(real, g, 0.0),
                          jnp.where(real, beta, 0.0), s0)
    return o, jax.lax.dynamic_update_slice(
        state, s[None, None].astype(state.dtype), (slot, li, 0, 0, 0))
