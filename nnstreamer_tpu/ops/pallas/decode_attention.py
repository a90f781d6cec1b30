"""Length-bounded decode attention over a KV store, as a Pallas TPU kernel.

One new token per stream attends the rows of its own K/V store that are
live and no others, and the token's own K/V row reaches the store without
the store being rewritten. The dense form (``window_attention_reference``)
runs the einsum over the whole ``max_len`` axis and masks, so every decode
step reads every row of every slot, and its per-stream
``dynamic_update_slice`` under a batch of positions becomes a masked
rewrite of the store; both costs grow with the store's capacity, not with
what the streams hold.

The kernel takes one layer's queries ``(B, H, 1, hd)``, the new rows, and
the stores in place (HBM refs aliased in and out). The wrapper flattens the
work into items ``(stream, KV block)``: stream ``b`` has
``ceil(pos[b] / block)`` of them (one at least) and a stream that holds no
request has none, so it costs no read at all. The kernel walks the items
with a two-deep DMA pipeline that runs across stream boundaries: block
``t + 1`` is in flight while block ``t`` is computed. A whole block is one
DMA of K and one of V. A stream's last block, where ``pos[b]`` ends inside
it (its tail), is fetched as sub-tiles of ``grain`` rows, only those that
hold a row below ``pos[b]``, so a length rounds up to the grain and not to
the block. The buffer's rows a tail did not fill hold an earlier item's
data (or the scratch's first bits): the tail's value rows at or past
``pos[b]`` are cleared before its arithmetic, so that no 0 x NaN reaches
the sum, and whole blocks pay nothing for it. The online-softmax state of a
stream starts from its new row (which therefore never has to be read back
from HBM), each item folds ``block`` rows in, and the stream's last item
writes the output. The new rows go to ``store[.., pos[b], :]`` by one small
DMA a stream, started before the first item and waited for after the
last.

Grouped-query attention (fewer K/V heads than query heads, each K/V head
serving ``group`` query heads in order) is read from the shapes: the
queries have ``group`` times the heads of the new rows and the store.

Arithmetic is float32 throughout. With equal heads (a group of one) it
runs on the VPU: a head's product with a block is a multiply and a lane
reduction, where the MXU would take its one query row as a tile of 8
rows. A group's query rows share a K/V head's block, so an item fetches
the block once and folds it into the whole group on the MXU, as two
float32 products at ``highest`` precision: the group's rows (padded with
zero rows to a sublane tile of 8, dropped at the output) against the
block's keys, then the probabilities against its values, the online
softmax's state lane-dense along the block. Which form runs follows from
the shapes alone.

Where the computation is not placed on a TPU the dense form runs
(``ops/pallas.per_platform``); it is also the reference the tests hold the
kernel to, and the form a window wider than one token takes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import per_platform

_NEG_INF = -1e30  # finite, so a fully masked block leaves exp() at 0
#: most rows of one K/V block: an item of the work list, and a whole
#: block's DMA. At 16 heads of 128: 128 rows leave the arithmetic's fixed
#: cost an item in sight (PERF.md, PR 26)
_BLOCK_ROWS = 256
#: most rows of one sub-tile of a stream's last block: the tail's DMA grain,
#: and what a length rounds up to (PERF.md, PR 36)
_TAIL_ROWS = 32
#: heads computed a trip of the item's loop over heads, so that their chains
#: of reductions overlap and a tail's arithmetic fits under the next item's
#: DMA. 16 (the loop unrolled whole) ran 0.7 % faster alone and took 0.09 s
#: longer to lower, paid again in every process's set-up (PERF.md, PR 36)
#: (K/V heads where the queries are grouped: at a group of 8, two a trip
#: ran 3 % faster than one, PERF.md §6)
_HEAD_UNROLL = 4


@functools.lru_cache(maxsize=None)
def kv_block(max_len: int) -> int:
    """Largest divisor of ``max_len`` that is a multiple of 8 (a float32
    sublane tile) and at most ``_BLOCK_ROWS``; 0 where there is none, and
    the dense form runs."""
    for b in range(min(_BLOCK_ROWS, max_len) // 8 * 8, 0, -8):
        if max_len % b == 0:
            return b
    return 0


def read_grain(max_len: int) -> int:
    """Rows a length rounds up to in this kernel's reads: one sub-tile of
    a stream's last block, the largest divisor of the block that divides
    ``_TAIL_ROWS`` (a multiple of 8, as the block is); 0 where no block
    size fits and the dense form reads every row."""
    block = kv_block(max_len)
    return math.gcd(block, _TAIL_ROWS) if block else 0


def rows_read(pos: int, max_len: int, grain: int) -> int:
    """Store rows one decode step at position ``pos`` asks of a kernel
    whose reads round up to ``grain`` rows (its module's ``read_grain``):
    what the engine counts in ``kv_rows_attended``; all ``max_len`` rows
    where the grain is 0."""
    if not grain:
        return max_len
    return -(-min(pos, max_len) // grain) * grain


def window_attention_reference(q, k_new, v_new, kc, vc, li, pos,
                               active=None, *, layer_axis: int):
    """Dense masked form: write the window's rows, attend ``max_len``.

    q: (B, H, W, hd); k_new, v_new: (B, H / group, W, hd), the K/V heads
    (K/V head i serves query heads ``i * group .. i * group + group - 1``);
    kc, vc: the 5-D store, layers on ``layer_axis`` (0: ``(L, B, H / group,
    max_len, hd)``, 1: ``(B, L, H / group, max_len, hd)``); li: the layer;
    pos: () for streams in step or (B,) per stream; active: None or (B,)
    bool. Row j of a stream attends columns <= pos + j. A stream that is
    not active writes nothing and gets its own value rows back (a K/V
    head's to each query head of its group); neither does a window past
    capacity write. Returns (o (B, H, W, hd), kc, vc)."""
    b, _, w, hd = q.shape
    group = q.shape[1] // k_new.shape[1]
    max_len = kc.shape[-2]
    bax = 1 - layer_axis
    k_new, v_new = k_new.astype(kc.dtype), v_new.astype(vc.dtype)
    if pos.ndim == 0:
        at = [0, 0, pos, 0]
        at.insert(layer_axis, li)
        shape = list(k_new.shape)
        shape.insert(layer_axis, 1)
        kc2 = jax.lax.dynamic_update_slice(kc, k_new.reshape(shape), at)
        vc2 = jax.lax.dynamic_update_slice(vc, v_new.reshape(shape), at)
        rows = jnp.broadcast_to(pos, (b,))
    else:
        write = jax.vmap(
            lambda c, new, p: jax.lax.dynamic_update_slice(
                c, new[None], (li, 0, p, 0)),
            in_axes=(bax, 0, 0), out_axes=bax)
        kc2, vc2 = write(kc, k_new, pos), write(vc, v_new, pos)
        rows = pos
    if active is not None:
        keep = (active & (rows + w <= max_len)).reshape(
            (b, 1, 1, 1, 1) if bax == 0 else (1, b, 1, 1, 1))
        kc2, vc2 = jnp.where(keep, kc2, kc), jnp.where(keep, vc2, vc)
    kc_l = jax.lax.dynamic_index_in_dim(kc2, li, layer_axis, keepdims=False)
    vc_l = jax.lax.dynamic_index_in_dim(vc2, li, layer_axis, keepdims=False)
    if group > 1:
        kc_l, vc_l, v_new = (jnp.repeat(z, group, axis=1)
                             for z in (kc_l, vc_l, v_new))
    live = (jnp.arange(max_len)[None, None, :]
            <= (rows[:, None] + jnp.arange(w)[None, :])[:, :, None])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc_l) / math.sqrt(hd)
    s = jnp.where(live[:, None], s, _NEG_INF)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vc_l)
    if active is not None:
        o = jnp.where(active[:, None, None, None], o, v_new.astype(o.dtype))
    return o, kc2, vc2


def lane_bounds(max_len: int, rows: int) -> tuple:
    """The store prefixes a prompt window's attention may read: powers of
    two from 256 rows (the window's width where that is more) up to
    ``max_len``, and ``max_len`` itself."""
    out, b = [], max(rows, 256)
    while b < max_len:
        out.append(b)
        b *= 2
    return tuple(out) + (max_len,)


def lane_window_attention(q, k_new, v_new, kc, vc, li, slot, pos0):
    """One layer's attention of a window of P prompt rows of ONE stream,
    with the store updated: the window's rows are written in place at
    ``store[slot, li, :, pos0:pos0+P]`` and row j attends the stream's
    columns ``<= pos0 + j``, read from the smallest prefix of
    :func:`lane_bounds` that holds ``pos0 + P`` rows, not ``max_len``.

    q: (P, H, 1, hd), the rows as a batch of one-token windows (the layout
    of the decode rows they ride with); k_new, v_new: (P, H / group, 1, hd),
    grouped as in :func:`window_attention_reference`; kc, vc: the store a
    slot, ``(S, L, H / group, max_len, hd)``; li, slot, pos0: scalars,
    ``pos0 + P <= max_len`` (the caller keeps windows aligned to P).
    Returns (o (P, H, 1, hd), kc, vc)."""
    p, _, _, hd = q.shape
    h = k_new.shape[1]
    group = q.shape[1] // h
    max_len = kc.shape[-2]
    pos0 = jnp.asarray(pos0, jnp.int32)
    rows = lambda z: z[:, :, 0].transpose(1, 0, 2)          # (H, P, hd)
    at = (slot, li, 0, pos0, 0)
    kc = jax.lax.dynamic_update_slice(
        kc, rows(k_new)[None, None].astype(kc.dtype), at)
    vc = jax.lax.dynamic_update_slice(
        vc, rows(v_new)[None, None].astype(vc.dtype), at)
    bounds = lane_bounds(max_len, p)

    def attend(bound):
        def dense(qh, kc, vc):
            size = (1, 1, h, bound, hd)
            kb = jax.lax.dynamic_slice(kc, (slot, li, 0, 0, 0), size)[0, 0]
            vb = jax.lax.dynamic_slice(vc, (slot, li, 0, 0, 0), size)[0, 0]
            live = jnp.arange(bound)[None, :] \
                <= (pos0 + jnp.arange(p))[:, None]
            if group > 1:
                # a K/V head's query heads as one block of group x P rows
                qh = qh.reshape(h, group * p, hd)
                live = jnp.tile(live, (group, 1))
            s = jnp.einsum("hqd,hkd->hqk", qh, kb) / math.sqrt(hd)
            s = jnp.where(live[None], s, _NEG_INF)
            o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), vb)
            return o.reshape(h * group, p, hd)
        return dense

    # the first prefix that holds the window's last row
    which = sum((pos0 + p > b).astype(jnp.int32) for b in bounds[:-1])
    o = jax.lax.switch(which, [attend(b) for b in bounds], rows(q), kc, vc)
    return o.transpose(1, 0, 2)[:, :, None].astype(q.dtype), kc, vc


def _decode_kernel(row0_ref, pos_ref, write_ref, n_items_ref, item_b_ref,
                   item_blk_ref, q_ref, kn_ref, vn_ref, kc_hbm, vc_hbm,
                   o_ref, kc_out, vc_out, kbuf, vbuf, sem, wsem, m_scr,
                   l_scr, acc_scr, *tile, n_streams: int, n_heads: int,
                   block: int, grain: int, max_len: int, sm_scale: float,
                   group: int = 1):
    del kc_hbm, vc_hbm  # the same buffers as kc_out / vc_out (aliased)
    n_items = n_items_ref[0]
    # Mosaic takes a one-row DMA into store rows one lane tile wide only;
    # wider rows go in through their tile of 8 (``tile``): read at the
    # start, the new row set in VMEM, written back at the end, when every
    # item has read what it reads
    row_dma = not tile

    def row_copies(b):
        at = (pl.ds(row0_ref[b], n_heads), pl.ds(pos_ref[b], 1))
        return (pltpu.make_async_copy(kn_ref.at[b], kc_out.at[at],
                                      wsem.at[0, b]),
                pltpu.make_async_copy(vn_ref.at[b], vc_out.at[at],
                                      wsem.at[1, b]))

    def tile_copies(b, back):
        """The 8-row tile of stream b's store that holds row pos[b], into
        ``tile`` or, ``back``, out of it."""
        first = pl.multiple_of(pos_ref[b] // 8 * 8, 8)
        at = (pl.ds(row0_ref[b], n_heads), pl.ds(first, 8))
        out = []
        for i, store in enumerate((kc_out, vc_out)):
            ends = (store.at[at], tile[0].at[i, b])
            src, dst = ends[::-1] if back else ends
            out.append(pltpu.make_async_copy(src, dst, wsem.at[i, b]))
        return out

    def set_row(b):
        at = jax.lax.broadcasted_iota(jnp.int32, tile[0].shape[2:], 1) \
            == pos_ref[b] % 8                           # (H, 8, hd)
        for i, new in enumerate((kn_ref, vn_ref)):
            tile[0][i, b] = jnp.where(at, new[b].astype(tile[0].dtype),
                                      tile[0][i, b])

    def live_rows(t):
        # rows of item t's block below its stream's position: block for
        # all but a stream's last block
        p = jnp.minimum(pos_ref[item_b_ref[t]], max_len)
        return jnp.minimum(p - item_blk_ref[t] * block, block)

    def block_copies(t, par, lo=0, rows=block):
        first = pl.multiple_of(item_blk_ref[t] * block, block) if \
            rows == block else pl.multiple_of(item_blk_ref[t] * block + lo,
                                              grain)
        at = (pl.ds(row0_ref[item_b_ref[t]], n_heads), pl.ds(first, rows))
        sub = (slice(None), pl.ds(lo, rows))
        return (pltpu.make_async_copy(kc_out.at[at], kbuf.at[par].at[sub],
                                      sem.at[0, par]),
                pltpu.make_async_copy(vc_out.at[at], vbuf.at[par].at[sub],
                                      sem.at[1, par]))

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    def item_copies(t, par, act):
        """``act`` (start or wait) on item t's DMAs: the whole block, or of
        a stream's last block the sub-tiles that hold a live row."""
        live = live_rows(t)
        pl.when(live == block)(functools.partial(act, block_copies(t, par)))

        @pl.when(live < block)
        def _tail():
            @pl.loop(0, -(-live // grain))
            def _sub_tile(j):
                act(block_copies(t, par, pl.multiple_of(j * grain, grain),
                                 grain))

    # a stream without items (no request) gets its own value row back (a
    # K/V head's to each query row of its group)
    o_ref[...] = jnp.broadcast_to(vn_ref[...], o_ref.shape).astype(
        o_ref.dtype)
    for b in range(n_streams):
        pl.when(write_ref[b] == 1)(functools.partial(
            start, row_copies(b) if row_dma else tile_copies(b, False)))
    pl.when(n_items > 0)(lambda: item_copies(0, 0, start))

    nt = (((1,), (1,)), ((), ()))      # contract the last axis of both
    f32 = dict(precision=jax.lax.Precision.HIGHEST,
               preferred_element_type=jnp.float32)

    def item(t, carry):
        par = t % 2
        pl.when(t + 1 < n_items)(
            lambda: item_copies(t + 1, 1 - par, start))
        b = item_b_ref[t]
        blk = item_blk_ref[t]
        p = jnp.minimum(pos_ref[b], max_len)

        @pl.when(blk == 0)
        def _from_new_row():
            m_scr[...] = jnp.sum(
                q_ref[b].astype(jnp.float32)
                * kn_ref[b].astype(jnp.float32),
                axis=-1, keepdims=True) * sm_scale  # (H, rows, 1)
            l_scr[...] = jnp.ones_like(l_scr)
            acc_scr[...] = jnp.broadcast_to(vn_ref[b].astype(jnp.float32),
                                            acc_scr.shape)

        item_copies(t, par, wait)
        n_live = p - blk * block

        @pl.when(n_live < block)
        def _clear_tail():
            # the tail's value rows at or past p: past its sub-tiles they
            # hold an earlier item's data, and 0 x NaN would reach the sum
            @pl.loop(n_live // grain, block // grain)
            def _sub_tile(j):
                at = (par, slice(None),
                      pl.ds(pl.multiple_of(j * grain, grain), grain))
                r = j * grain + jax.lax.broadcasted_iota(
                    jnp.int32, (grain, vbuf.shape[-1]), 0)
                vbuf[at] = jnp.where(r < n_live, vbuf[at], 0)

        # the block's rows down a column of scores (one query head's) or,
        # grouped, along a row of them (each query row's)
        shape, axis = ((block, 1), 0) if group == 1 else ((1, block), 1)
        rows = blk * block + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        live = rows < p

        def fold(k, v, h):
            """Query head h's online softmax over its K/V head's block (k,
            v: (block, hd)), on the VPU."""
            s = jnp.sum(k * q_ref[b, h].astype(jnp.float32), axis=-1,
                        keepdims=True) * sm_scale       # (block, 1)
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_scr[h]                           # (1, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(pr, axis=0, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jnp.sum(
                pr * v, axis=0, keepdims=True)          # (1, hd)
            m_scr[h] = m_new

        def fold_group(k, v, h):
            """The online softmax of K/V head h's query rows (its group,
            padded to whole sublane tiles) over its block, as two products
            on the MXU."""
            s = jax.lax.dot_general(q_ref[b, h].astype(jnp.float32), k, nt,
                                    **f32)              # (rows, block)
            s = jnp.where(live, s * sm_scale, _NEG_INF)
            m_prev = m_scr[h]                           # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(pr, axis=1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jnp.dot(pr, v, **f32)
            m_scr[h] = m_new

        def head(h):
            k = kbuf[par, h].astype(jnp.float32)        # (block, hd)
            v = vbuf[par, h].astype(jnp.float32)
            (fold if group == 1 else fold_group)(k, v, h)

        def heads(i, c):
            for j in range(unroll):
                head(i * unroll + j)
            return c

        unroll = math.gcd(n_heads, _HEAD_UNROLL)
        jax.lax.fori_loop(0, n_heads // unroll, heads, 0)

        @pl.when((blk + 1) * block >= p)
        def _last_block():
            o_ref[b] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n_items, item, 0)
    for b in range(n_streams):
        if row_dma:
            pl.when(write_ref[b] == 1)(functools.partial(wait,
                                                         row_copies(b)))
            continue

        @pl.when(write_ref[b] == 1)
        def _row_in_tile(b=b):
            wait(tile_copies(b, False))
            set_row(b)
            start(tile_copies(b, True))

    for b in range(0 if row_dma else n_streams):
        pl.when(write_ref[b] == 1)(functools.partial(wait,
                                                     tile_copies(b, True)))


def _decode_pallas(q, k_new, v_new, kc, vc, li, pos, active=None, *,
                   layer_axis: int, block: int, grain: int, interpret: bool):
    b, hq, _, hd = q.shape
    h = k_new.shape[1]                                  # K/V heads
    max_len = kc.shape[-2]
    n_layers = kc.shape[layer_axis]
    li = jnp.asarray(li, jnp.int32)
    pos = jnp.broadcast_to(pos.astype(jnp.int32), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    streams = jnp.arange(b, dtype=jnp.int32)
    # first store row of this layer's heads, a stream
    row0 = ((li * b + streams) if layer_axis == 0
            else (streams * n_layers + li)) * h
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
    flat = (kc.shape[0] * kc.shape[1] * h, max_len, hd)
    group = hq // h
    if group > 1:
        # a K/V head's query rows as one slab of whole sublane tiles, the
        # padding zeros, dropped from the output
        padded = -(-group // 8) * 8
        q = jnp.pad(q.reshape(b, h, group, hd),
                    ((0, 0), (0, 0), (0, padded - group), (0, 0)))
    kernel = functools.partial(
        _decode_kernel, n_streams=b, n_heads=h, block=block, grain=grain,
        max_len=max_len, sm_scale=1.0 / math.sqrt(hd), group=group)
    # a new row wider than one lane tile goes in through its tile of 8
    tile = [] if hd == 128 else [pltpu.VMEM((2, b, h, 8, hd), kc.dtype)]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, kc2, vc2 = pl.pallas_call(
        kernel,
        in_specs=[smem] * 6 + [vmem] * 3 + [hbm] * 2,
        out_specs=[vmem, hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(flat, kc.dtype),
                   jax.ShapeDtypeStruct(flat, vc.dtype)],
        scratch_shapes=[
            pltpu.VMEM((2, h, block, hd), kc.dtype),
            pltpu.VMEM((2, h, block, hd), vc.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, b)),
            # the online softmax's state a query row: (H, 1) or, grouped,
            # (K/V heads, the group's rows)
            pltpu.VMEM(q.shape[1:3] + (1,), jnp.float32),
            pltpu.VMEM(q.shape[1:3] + (1,), jnp.float32),
            pltpu.VMEM(q.shape[1:3] + (hd,), jnp.float32),
        ] + tile,
        input_output_aliases={9: 1, 10: 2},
        # two buffers each of K and V, the tiles, and room for the item's
        # temporaries
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * h * block * hd * 4
            + (2 * b * h * 8 * hd * 4 if tile else 0) + (16 << 20)),
        name="decode_attention",
        interpret=interpret,
    )(row0, pos, write, ends[-1:].astype(jnp.int32), item_b, item_blk,
      q, k_new.astype(kc.dtype), v_new.astype(vc.dtype),
      kc.reshape(flat), vc.reshape(flat))
    if group > 1:
        o = o[:, :, :group].reshape(b, hq, 1, hd)
    return o, kc2.reshape(kc.shape), vc2.reshape(vc.shape)


@functools.partial(jax.jit, static_argnames=("layer_axis", "interpret"))
def decode_attention(q, k_new, v_new, kc, vc, li, pos, active=None, *,
                     layer_axis: int, interpret: bool = False):
    """One layer's attention of a one-token decode step, with the store
    updated: the rows ``< pos[b]`` of each active stream and its new row
    are attended, and the new row is written at ``pos[b]``.

    Arguments and results as :func:`window_attention_reference` with a
    window of one; the queries' heads over the new rows' give the group. On a TPU the kernel runs; elsewhere, and where no
    block size divides ``max_len``, the head size is not a multiple of
    the 128 lanes (Mosaic refuses the one-row DMA out of a padded row) or
    the store is not float32, the dense form. ``interpret=True`` (tests) runs the kernel through the Pallas
    interpreter. Jitted, so that the unrolled layers of a decode step
    trace and lower the kernel once and not once a layer."""
    max_len, hd = kc.shape[-2:]
    block = kv_block(max_len)
    reference = functools.partial(window_attention_reference,
                                  layer_axis=layer_axis)
    args = (q, k_new, v_new, kc, vc, li, pos)
    if active is not None:
        args += (active,)
    if not block or hd % 128 or kc.dtype != jnp.float32 \
            or vc.dtype != jnp.float32:
        return reference(*args)
    kernel = functools.partial(_decode_pallas, layer_axis=layer_axis,
                               block=block, grain=read_grain(max_len),
                               interpret=interpret)
    return per_platform(kernel, reference, interpret, *args)
