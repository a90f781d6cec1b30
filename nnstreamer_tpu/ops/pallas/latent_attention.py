"""Absorbed latent attention over a latent store, dense and as a kernel.

Latent (multi-head latent) attention keeps ONE row a token a layer, shared
by all heads: the normed latent ``c`` (C wide) and the rotary key ``r`` (R
wide, kept padded to the 128 lanes). In the absorbed form a head's query is
carried into the latent space (``qa = q_nope W_k``, (H, C)), its score
against a stored token is ``qa . c + qr . r``, and the value it sums is the
latent row itself (``o = sum p c``; the caller carries it out through
``W_v``). So a decode step reads each live row once for all heads.

``latent_decode_attention`` is ``decode_attention``'s counterpart: the work
is items (stream, 256-row block) walked with a two-deep DMA pipeline, a
stream that holds no request has none, the online-softmax state of a
stream starts from its new row, and the new rows reach the aliased stores
by one-row DMAs. Unlike there a block serves all heads at once, so the two
products of an item are (H, C) x (C, rows) and (H, rows) x (rows, C) on the
MXU, float32 at ``highest``.

``latent_window_reference`` is the dense masked form over the whole
``max_len`` axis: the form every platform but a TPU takes, and what the
tests hold the kernel to. ``latent_lane_attention`` is the prompt lane's:
a window of P rows of one stream, written in place and attended over the
smallest power-of-two prefix that holds them (``decode_attention
.lane_bounds``), dense on every platform.

Stores: ``cc`` ``(S, L, C / 128, max_len, 128)``, the latent as planes of
128 lanes (a row of the plain ``(max_len, C)`` layout lies in C / 128 tiles
of 8 rows, and Mosaic refuses the one-row DMA into it; a plane's row is one
contiguous line, as a head's row is in ``decode_attention``'s stores), and
``rc`` ``(S, L, max_len, 128)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import per_platform
from .decode_attention import kv_block, lane_bounds

_HI = jax.lax.Precision.HIGHEST
_NEG_INF = -1e30
#: the lanes of a vreg: the width of a latent plane, and of the rotary
#: key's row in its store (padded: Mosaic refuses a one-row DMA out of a
#: padded row)
LANES = 128


def _pad_last(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def to_planes(c):
    """(.., rows, C) latent rows as planes of 128 lanes, (.., C / 128, rows,
    128): the store's layout."""
    *lead, rows, width = c.shape
    return jnp.moveaxis(c.reshape(*lead, rows, width // LANES, LANES), -2, -3)


def from_planes(p):
    """The inverse of :func:`to_planes`."""
    *lead, g, rows, lanes = p.shape
    return jnp.moveaxis(p, -3, -2).reshape(*lead, rows, g * lanes)


def latent_window_reference(qa, qr, c_new, r_new, cc, rc, li, pos,
                            active=None, *, sm_scale: float):
    """Dense masked form of one layer's one-token step: write each active
    stream's new row at ``pos[b]``, attend the whole ``max_len`` axis.

    qa: (B, H, C) absorbed queries; qr: (B, H, R) rotary queries; c_new:
    (B, C), r_new: (B, R) the new rows; cc, rc: the stores; li: the layer;
    pos: (B,); active: None or (B,) bool. A stream that is not active, or
    is past capacity, writes nothing; one that is not active gets its own
    latent row back. Returns (o (B, H, C), cc, rc)."""
    b = qa.shape[0]
    max_len = rc.shape[2]
    r_pad = _pad_last(r_new, rc.shape[-1]).astype(rc.dtype)
    cc2 = jax.vmap(lambda s, new, p: jax.lax.dynamic_update_slice(
        s, new[None], (li, 0, p, 0)))(
            cc, to_planes(c_new[:, None].astype(cc.dtype)), pos)
    rc2 = jax.vmap(lambda s, new, p: jax.lax.dynamic_update_slice(
        s, new[None, None], (li, p, 0)))(rc, r_pad, pos)
    if active is not None:
        keep = active & (pos < max_len)
        cc2 = jnp.where(keep.reshape(b, 1, 1, 1, 1), cc2, cc)
        rc2 = jnp.where(keep.reshape(b, 1, 1, 1), rc2, rc)
    c_l = from_planes(
        jax.lax.dynamic_index_in_dim(cc2, li, 1, keepdims=False))
    r_l = jax.lax.dynamic_index_in_dim(rc2, li, 1, keepdims=False)
    s = jnp.einsum("bhc,bkc->bhk", qa, c_l, precision=_HI) + jnp.einsum(
        "bhr,bkr->bhk", qr, r_l[..., :qr.shape[-1]], precision=_HI)
    live = jnp.arange(max_len)[None, :] <= pos[:, None]
    s = jnp.where(live[:, None], s * sm_scale, _NEG_INF)
    o = jnp.einsum("bhk,bkc->bhc", jax.nn.softmax(s, axis=-1), c_l,
                   precision=_HI)
    if active is not None:
        o = jnp.where(active[:, None, None], o,
                      c_new[:, None].astype(o.dtype))
    return o, cc2, rc2


def latent_lane_attention(qa, qr, c_new, r_new, cc, rc, li, slot, pos0, *,
                          sm_scale: float):
    """One layer's attention of a window of P prompt rows of ONE stream,
    in the absorbed form, with the stores updated: the window's rows are
    written at the store's rows ``pos0 .. pos0 + P - 1`` of ``slot`` and row
    j attends the stream's rows ``<= pos0 + j``, read from the smallest
    prefix of ``lane_bounds`` that holds them.

    qa: (P, H, C); qr: (P, H, R); c_new: (P, C); r_new: (P, R); li, slot,
    pos0: scalars with ``pos0 + P <= max_len``. Returns (o (P, H, C), cc,
    rc)."""
    p, h, c = qa.shape
    g = cc.shape[2]
    max_len = rc.shape[2]
    pos0 = jnp.asarray(pos0, jnp.int32)
    cc = jax.lax.dynamic_update_slice(
        cc, to_planes(c_new.astype(cc.dtype))[None, None],
        (slot, li, 0, pos0, 0))
    rc = jax.lax.dynamic_update_slice(
        rc, _pad_last(r_new, rc.shape[-1])[None, None].astype(rc.dtype),
        (slot, li, pos0, 0))
    bounds = lane_bounds(max_len, p)
    qa = qa.reshape(p, h, g, LANES)
    qr = _pad_last(qr, rc.shape[-1])     # the store's pad lanes hold zeros

    def attend(bound):
        def dense(qa, qr, cc, rc):
            cb = jax.lax.dynamic_slice(
                cc, (slot, li, 0, 0, 0), (1, 1, g, bound, LANES))[0, 0]
            rb = jax.lax.dynamic_slice(
                rc, (slot, li, 0, 0), (1, 1, bound, rc.shape[-1]))[0, 0]
            s = jnp.einsum("phgc,gkc->phk", qa, cb, precision=_HI) \
                + jnp.einsum("phr,kr->phk", qr, rb, precision=_HI)
            live = jnp.arange(bound)[None, :] \
                <= (pos0 + jnp.arange(p))[:, None]
            s = jnp.where(live[:, None], s * sm_scale, _NEG_INF)
            return jnp.einsum("phk,gkc->phgc", jax.nn.softmax(s, axis=-1),
                              cb, precision=_HI)
        return dense

    which = sum((pos0 + p > b).astype(jnp.int32) for b in bounds[:-1])
    o = jax.lax.switch(which, [attend(b) for b in bounds], qa, qr, cc, rc)
    return o.reshape(p, h, c), cc, rc


def _latent_kernel(row0_ref, pos_ref, write_ref, n_items_ref, item_b_ref,
                   item_blk_ref, qa_ref, qr_ref, cn_ref, rn_ref, cc_hbm,
                   rc_hbm, o_ref, cc_out, rc_out, cbuf, rbuf, sem, wsem,
                   m_scr, l_scr, acc_scr, *, n_streams: int, planes: int,
                   block: int, max_len: int, sm_scale: float):
    del cc_hbm, rc_hbm  # the same buffers as cc_out / rc_out (aliased)
    n_items = n_items_ref[0]
    lanes = [slice(g * LANES, (g + 1) * LANES) for g in range(planes)]

    def row_copies(b):
        row = pl.ds(pos_ref[b], 1)
        return (pltpu.make_async_copy(
                    cn_ref.at[b],
                    cc_out.at[pl.ds(row0_ref[b] * planes, planes), row],
                    wsem.at[0, b]),
                pltpu.make_async_copy(
                    rn_ref.at[b], rc_out.at[pl.ds(row0_ref[b], 1), row],
                    wsem.at[1, b]))

    def block_copies(t, par):
        row0 = row0_ref[item_b_ref[t]]
        rows = pl.ds(pl.multiple_of(item_blk_ref[t] * block, block), block)
        return (pltpu.make_async_copy(
                    cc_out.at[pl.ds(row0 * planes, planes), rows],
                    cbuf.at[par], sem.at[0, par]),
                pltpu.make_async_copy(
                    rc_out.at[row0, rows], rbuf.at[par], sem.at[1, par]))

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    # a stream without items (no request) gets its own latent row back
    for g in range(planes):
        o_ref[:, :, lanes[g]] = jnp.broadcast_to(
            cn_ref[:, g], o_ref.shape[:2] + (LANES,))
    for b in range(n_streams):
        pl.when(write_ref[b] == 1)(functools.partial(start, row_copies(b)))
    pl.when(n_items > 0)(lambda: start(block_copies(0, 0)))

    nt = (((1,), (1,)), ((), ()))      # contract the last axis of both
    f32 = dict(precision=_HI, preferred_element_type=jnp.float32)

    def item(t, carry):
        par = t % 2
        pl.when(t + 1 < n_items)(
            lambda: start(block_copies(t + 1, 1 - par)))
        b = item_b_ref[t]
        blk = item_blk_ref[t]
        p = jnp.minimum(pos_ref[b], max_len)
        qr = qr_ref[b]                                   # (H, lanes)

        @pl.when(blk == 0)
        def _from_new_row():
            m = jnp.sum(qr * rn_ref[b, 0], axis=-1, keepdims=True)
            for g in range(planes):
                m += jnp.sum(qa_ref[b, :, lanes[g]] * cn_ref[b, g], axis=-1,
                             keepdims=True)
                acc_scr[:, lanes[g]] = jnp.broadcast_to(
                    cn_ref[b, g], (acc_scr.shape[0], LANES))
            m_scr[...] = m * sm_scale                    # (H, 1)
            l_scr[...] = jnp.ones_like(l_scr)

        wait(block_copies(t, par))
        s = jax.lax.dot_general(qr, rbuf[par], nt, **f32)
        for g in range(planes):
            s += jax.lax.dot_general(qa_ref[b, :, lanes[g]], cbuf[par, g],
                                     nt, **f32)
        rows = blk * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)
        s = jnp.where(rows < p, s * sm_scale, _NEG_INF)  # (H, block)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(pr, axis=1, keepdims=True)
        for g in range(planes):
            acc_scr[:, lanes[g]] = alpha * acc_scr[:, lanes[g]] + jnp.dot(
                pr, cbuf[par, g], **f32)
        m_scr[...] = m_new

        @pl.when((blk + 1) * block >= p)
        def _last_block():
            o_ref[b] = acc_scr[...] / l_scr[...]

        return carry

    jax.lax.fori_loop(0, n_items, item, 0)
    for b in range(n_streams):
        pl.when(write_ref[b] == 1)(functools.partial(wait, row_copies(b)))


def _latent_pallas(qa, qr, c_new, r_new, cc, rc, li, pos, active=None, *,
                   sm_scale: float, block: int, interpret: bool):
    b, h, c = qa.shape
    n_slots, n_layers, g, max_len, _ = cc.shape
    hp = -(-h // 8) * 8          # heads as whole sublane tiles
    li = jnp.asarray(li, jnp.int32)
    pos = pos.astype(jnp.int32)
    if active is None:
        active = jnp.ones((b,), bool)
    row0 = jnp.arange(b, dtype=jnp.int32) * n_layers + li
    # the work list: stream b's blocks 0 .. ceil(pos / block) - 1, streams
    # in order, none for a stream that holds no request
    n_max = max_len // block
    nblk = jnp.where(
        active, jnp.maximum(-(-jnp.minimum(pos, max_len) // block), 1), 0)
    ends = jnp.cumsum(nblk)
    t = jnp.arange(b * n_max, dtype=jnp.int32)
    item_b = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1), b - 1
    ).astype(jnp.int32)
    item_blk = jnp.clip(t - (ends - nblk)[item_b], 0, n_max - 1)
    write = (active & (pos < max_len)).astype(jnp.int32)
    pad_h = lambda z: jnp.pad(z, ((0, 0), (0, hp - h), (0, 0)))  # noqa: E731
    flat_c = (n_slots * n_layers * g, max_len, LANES)
    flat_r = (n_slots * n_layers, max_len, LANES)
    kernel = functools.partial(
        _latent_kernel, n_streams=b, planes=g, block=block, max_len=max_len,
        sm_scale=sm_scale)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, cc2, rc2 = pl.pallas_call(
        kernel,
        in_specs=[smem] * 6 + [vmem] * 4 + [hbm] * 2,
        out_specs=[vmem, hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct((b, hp, c), jnp.float32),
                   jax.ShapeDtypeStruct(flat_c, cc.dtype),
                   jax.ShapeDtypeStruct(flat_r, rc.dtype)],
        scratch_shapes=[
            pltpu.VMEM((2, g, block, LANES), cc.dtype),
            pltpu.VMEM((2, block, LANES), rc.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, b)),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, c), jnp.float32),
        ],
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * block * (c + LANES) * 4
            + 3 * b * hp * (c + LANES) * 4 + (16 << 20)),
        name="mla_decode_attention",
        interpret=interpret,
    )(row0, pos, write, ends[-1:].astype(jnp.int32), item_b, item_blk,
      pad_h(qa), pad_h(_pad_last(qr, LANES)),
      to_planes(c_new.astype(cc.dtype)[:, None]),
      _pad_last(r_new, LANES).astype(rc.dtype)[:, None, None],
      cc.reshape(flat_c), rc.reshape(flat_r))
    return o[:, :h], cc2.reshape(cc.shape), rc2.reshape(rc.shape)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def latent_decode_attention(qa, qr, c_new, r_new, cc, rc, li, pos,
                            active=None, *, sm_scale: float,
                            interpret: bool = False):
    """One layer's absorbed latent attention of a one-token decode step,
    with the stores updated: the rows ``< pos[b]`` of each active stream
    and its new row are attended, and the new row is written at ``pos[b]``.

    Arguments and results as :func:`latent_window_reference`. On a TPU the
    kernel runs; elsewhere, and where no block size divides ``max_len`` or
    a store is not float32, the dense form. ``interpret=True`` (tests) runs
    the kernel through the Pallas interpreter."""
    block = kv_block(rc.shape[2])
    reference = functools.partial(latent_window_reference, sm_scale=sm_scale)
    args = (qa, qr, c_new, r_new, cc, rc, li, pos)
    if active is not None:
        args += (active,)
    if not block or cc.dtype != jnp.float32 or rc.dtype != jnp.float32:
        return reference(*args)
    kernel = functools.partial(_latent_pallas, sm_scale=sm_scale,
                               block=block, interpret=interpret)
    return per_platform(kernel, reference, interpret, *args)
