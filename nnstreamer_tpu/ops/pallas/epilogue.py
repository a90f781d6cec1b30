"""Pallas TPU kernels for the post-filter epilogue hot path (ops.epilogue).

The filter→transform/decoder tail is where streaming pipelines lose their
roofline after the GEMMs ("Pushing Tensor Accelerators Beyond MatMul",
PAPERS.md): SSD box decode + greedy NMS, classification argmax/top-k,
segmentation colorize, and w8a8 dequant→activation→requant chains either
ran as unfused lax ops or on host NumPy. These kernels back the epilogue
fuser (ops/epilogue.py) and the decoders' device-reduce paths:

  * ``nms_sweep``            — greedy NMS alive-sweep over the top-K
    score-sorted candidates (IoU matrix + sequential suppression).
  * ``class_reduce``         — per-anchor best class score + index
    (argmax/max over the class axis).
  * ``segment_colorize``     — per-pixel argmax over class logits + RGBA
    palette lookup via a one-hot MXU matmul.
  * ``dequant_gelu_requant`` — int32 GEMM accumulator → f32 dequant →
    gelu → per-row int8 requant, keeping the w8a8 MLP int8 end-to-end.

Every kernel has a jnp reference; the entry points choose between the
two per lowering platform (``per_platform``): a program placed on a TPU
gets the Mosaic kernel, the same program placed anywhere else gets the
reference. ``interpret=True`` forces the Pallas body through the
interpreter (tests). Fused callers rely on the references
matching the unfused lax/NumPy paths bit-for-bit, so change them in
lockstep with their consumers (decoders/bounding_box.py,
decoders/image_segment.py, ops/int8.py).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs import profile as _profile
from . import per_platform

_LANE = 128


# --------------------------------------------------------------------------- #
# nms_sweep: greedy suppression sweep over score-descending candidates
# --------------------------------------------------------------------------- #

def nms_sweep_reference(x0: jax.Array, y0: jax.Array, x1: jax.Array,
                        y1: jax.Array, scores: jax.Array,
                        iou_threshold: float, threshold: float) -> jax.Array:
    """Scores after greedy NMS: suppressed/below-threshold rows become -1.

    Candidates must already be score-descending (lax.top_k order); the
    sweep then matches decoders.util.nms exactly: a row is kept iff no
    earlier *kept* row overlaps it with IoU strictly above the threshold.
    """
    k = scores.shape[0]
    area = (x1 - x0) * (y1 - y0)
    ix = (jnp.minimum(x1[:, None], x1[None, :])
          - jnp.maximum(x0[:, None], x0[None, :]))
    iy = (jnp.minimum(y1[:, None], y1[None, :])
          - jnp.maximum(y0[:, None], y0[None, :]))
    inter = jnp.clip(ix, 0) * jnp.clip(iy, 0)
    union = area[:, None] + area[None, :] - inter
    iou = jnp.where(union > 0, inter / union, 0.0)
    later = jnp.arange(k)[None, :] > jnp.arange(k)[:, None]
    suppresses = (iou > iou_threshold) & later

    def body(i, alive):
        return alive & ~(alive[i] & suppresses[i])

    alive = jax.lax.fori_loop(0, k, body, scores >= threshold)
    return jnp.where(alive, scores, -1.0)


def _nms_kernel(cols_ref, rows_ref, o_ref, sup_ref, *, k: int,
                iou_thr: float, threshold: float):
    # the candidates arrive in both orientations so the (kp, kp) pair
    # matrices form by broadcast alone (no in-kernel transposes):
    # cols_ref (kp, 128) — candidate i down the sublanes, x0/y0/x1/y1 in
    # lanes 0-3; rows_ref (8, kp) — candidate j along the lanes,
    # x0/y0/x1/y1/score in sublanes 0-4
    cols = cols_ref[...]
    rows = rows_ref[...]
    x0c, y0c = cols[:, 0:1], cols[:, 1:2]              # (kp, 1)
    x1c, y1c = cols[:, 2:3], cols[:, 3:4]
    x0r, y0r = rows[0:1, :], rows[1:2, :]              # (1, kp)
    x1r, y1r = rows[2:3, :], rows[3:4, :]
    sc = rows[4:5, :]
    area_c = (x1c - x0c) * (y1c - y0c)
    area_r = (x1r - x0r) * (y1r - y0r)
    ix = jnp.minimum(x1c, x1r) - jnp.maximum(x0c, x0r)  # (kp, kp)
    iy = jnp.minimum(y1c, y1r) - jnp.maximum(y0c, y0r)
    inter = jnp.clip(ix, 0) * jnp.clip(iy, 0)
    union = area_c + area_r - inter
    iou = jnp.where(union > 0, inter / union, 0.0)
    kp = cols.shape[0]
    later = (jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 1)
             > jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 0))
    # row i of the scratch = who candidate i suppresses; the sweep reads
    # one row per step straight from VMEM (Mosaic has no dynamic slice of
    # a value, only of a ref)
    sup_ref[...] = jnp.where((iou > iou_thr) & later, 1.0, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)

    def body(i, alive):                                # alive (1, kp) 0/1
        alive_i = jnp.sum(jnp.where(lane == i, alive, 0.0),
                          axis=1, keepdims=True)       # (1, 1)
        return alive * (1.0 - alive_i * sup_ref[pl.ds(i, 1), :])

    alive = jax.lax.fori_loop(0, k, body,
                              jnp.where(sc >= threshold, 1.0, 0.0))
    o_ref[...] = jnp.broadcast_to(jnp.where(alive > 0.0, sc, -1.0),
                                  o_ref.shape)


def _nms_pallas(x0, y0, x1, y1, scores, *, iou_threshold: float,
                threshold: float, interpret: bool) -> jax.Array:
    k = scores.shape[0]
    kp = -(-k // _LANE) * _LANE
    fields = jnp.stack([x0, y0, x1, y1, scores]).astype(jnp.float32)
    # pad slots carry score -1: dead from the start, never kept/suppress
    rows = jnp.zeros((8, kp), jnp.float32).at[4, :].set(-1.0)
    rows = rows.at[:5, :k].set(fields)
    cols = jnp.zeros((kp, _LANE), jnp.float32).at[:k, :4].set(fields[:4].T)
    out = pl.pallas_call(
        functools.partial(_nms_kernel, k=k, iou_thr=float(iou_threshold),
                          threshold=float(threshold)),
        out_shape=jax.ShapeDtypeStruct((8, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kp, kp), jnp.float32)],
        interpret=interpret,
    )(cols, rows)
    return out[0, :k].astype(scores.dtype)


def nms_sweep(x0: jax.Array, y0: jax.Array, x1: jax.Array, y1: jax.Array,
              scores: jax.Array, *, iou_threshold: float, threshold: float,
              interpret: bool = False) -> jax.Array:
    """Greedy-NMS sweep on the VPU; jnp reference off-TPU.

    K is the PRE_NMS_TOPK candidate budget (≤ a few hundred), so the
    whole (K, K) IoU matrix fits one VMEM block — no grid.
    """
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.nms_sweep", scores.shape, scores.dtype)
    return per_platform(
        functools.partial(_nms_pallas, iou_threshold=iou_threshold,
                          threshold=threshold, interpret=interpret),
        functools.partial(nms_sweep_reference, iou_threshold=iou_threshold,
                          threshold=threshold),
        interpret, x0, y0, x1, y1, scores)


# --------------------------------------------------------------------------- #
# class_reduce: best class score + index per anchor
# --------------------------------------------------------------------------- #

def class_reduce_reference(cls: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return jnp.max(cls, axis=-1), jnp.argmax(cls, axis=-1)


def _class_reduce_kernel(x_ref, s_ref, i_ref, *, l: int):
    x = x_ref[...]                                    # (bn, lp) f32
    best = jnp.max(x, axis=1, keepdims=True)          # (bn, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # first-max index == argmax tie-break
    idx = jnp.min(jnp.where(x == best, iota, l), axis=1, keepdims=True)
    s_ref[...] = jnp.broadcast_to(best, s_ref.shape)
    i_ref[...] = jnp.broadcast_to(idx, i_ref.shape)


def _class_reduce_pallas(cls: jax.Array, *, interpret: bool
                         ) -> Tuple[jax.Array, jax.Array]:
    n, l = cls.shape
    lp = -(-l // _LANE) * _LANE
    block_rows = min(max(8, -(-n // 8) * 8), 512)
    np_ = -(-max(n, 1) // block_rows) * block_rows
    x = jnp.full((np_, lp), -jnp.inf, jnp.float32)
    x = x.at[:n, :l].set(cls.astype(jnp.float32))
    grid = (np_ // block_rows,)
    best, idx = pl.pallas_call(
        functools.partial(_class_reduce_kernel, l=l),
        out_shape=(jax.ShapeDtypeStruct((np_, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((np_, _LANE), jnp.int32)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, lp), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0))),
        interpret=interpret,
    )(x)
    return best[:n, 0].astype(cls.dtype), idx[:n, 0]


def class_reduce(cls: jax.Array,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(N, L) class scores → (best_score (N,), best_index (N,))."""
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.class_reduce", cls.shape, cls.dtype)
    return per_platform(
        functools.partial(_class_reduce_pallas, interpret=interpret),
        class_reduce_reference, interpret, cls)


# --------------------------------------------------------------------------- #
# segment_colorize: per-pixel argmax + RGBA palette lookup
# --------------------------------------------------------------------------- #

def segment_colorize_reference(x: jax.Array, palette: Any,
                               pre_argmaxed: bool = False) -> jax.Array:
    pal = jnp.asarray(palette)
    classes = x.astype(jnp.int32) if pre_argmaxed else jnp.argmax(x, axis=-1)
    return jnp.take(pal, classes.astype(jnp.int32), axis=0)


def _colorize_kernel(c_ref, pal_ref, o_ref):
    cid = c_ref[...][:, 0:1]                          # (bp, 1) int32
    iota = jax.lax.broadcasted_iota(jnp.int32, (cid.shape[0], 256), 1)
    onehot = (cid == iota).astype(jnp.float32)        # (bp, 256)
    out = jnp.dot(onehot, pal_ref[...],
                  preferred_element_type=jnp.float32)  # (bp, 128)
    # palette entries are <256 and exact in f32, so the hop is lossless
    o_ref[...] = out.astype(jnp.int32).astype(jnp.uint8)


def _argmax_colorize_kernel(x_ref, pal_ref, o_ref, *, c: int):
    x = x_ref[...]                                    # (bp, cp) f32
    best = jnp.max(x, axis=1, keepdims=True)
    iota1 = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    cid = jnp.min(jnp.where(x == best, iota1, c), axis=1, keepdims=True)
    iota2 = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 256), 1)
    onehot = (cid == iota2).astype(jnp.float32)
    out = jnp.dot(onehot, pal_ref[...],
                  preferred_element_type=jnp.float32)
    o_ref[...] = out.astype(jnp.int32).astype(jnp.uint8)


def _segment_colorize_pallas(x: jax.Array, *, palette: np.ndarray,
                             pre_argmaxed: bool, interpret: bool
                             ) -> jax.Array:
    pal = np.zeros((256, _LANE), np.float32)
    pal[:palette.shape[0], :palette.shape[1]] = palette
    if pre_argmaxed:
        lead = x.shape
        flat = x.reshape(-1).astype(jnp.int32)
        p = flat.shape[0]
        block_rows = min(max(32, -(-p // 32) * 32), 512)
        pp = -(-max(p, 1) // block_rows) * block_rows
        cids = jnp.zeros((pp, _LANE), jnp.int32).at[:p, 0].set(flat)
        kernel = _colorize_kernel
        inp = cids
        in_block = (block_rows, _LANE)
    else:
        lead = x.shape[:-1]
        c = x.shape[-1]
        flat = x.reshape(-1, c)
        p = flat.shape[0]
        cp = -(-c // _LANE) * _LANE
        block_rows = min(max(32, -(-p // 32) * 32), 512)
        pp = -(-max(p, 1) // block_rows) * block_rows
        xpad = jnp.full((pp, cp), -jnp.inf, jnp.float32)
        xpad = xpad.at[:p, :c].set(flat.astype(jnp.float32))
        kernel = functools.partial(_argmax_colorize_kernel, c=c)
        inp = xpad
        in_block = (block_rows, cp)
    grid = (pp // block_rows,)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((pp, _LANE), jnp.uint8),
        grid=grid,
        in_specs=[pl.BlockSpec(in_block, lambda i: (i, 0)),
                  pl.BlockSpec((256, _LANE), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(inp, jnp.asarray(pal))
    return out[:p, :4].reshape(tuple(lead) + (4,))


def segment_colorize(x: jax.Array, palette: Any, pre_argmaxed: bool = False,
                     interpret: bool = False) -> jax.Array:
    """(..., C) logits (or (...) class ids when pre_argmaxed) → (..., 4)
    RGBA uint8 via a (256, 4) palette, fused argmax+gather on device.

    The palette gather runs as a one-hot matmul on the MXU — palette
    values are uint8 (< 256, exact in f32), so the result is identical
    to ``palette[argmax(x, -1)]`` on host.
    """
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.segment_colorize", x.shape, x.dtype)
    palette = np.asarray(palette)
    return per_platform(
        functools.partial(_segment_colorize_pallas, palette=palette,
                          pre_argmaxed=pre_argmaxed, interpret=interpret),
        functools.partial(segment_colorize_reference, palette=palette,
                          pre_argmaxed=pre_argmaxed),
        interpret, x)


# --------------------------------------------------------------------------- #
# dequant_gelu_requant: w8a8 MLP inner epilogue, int8 end-to-end
# --------------------------------------------------------------------------- #

def dequant_gelu_requant_reference(y: jax.Array, xs: jax.Array, ws: jax.Array,
                                   out_dtype=jnp.bfloat16
                                   ) -> Tuple[jax.Array, jax.Array]:
    """int32 accumulator → dequant → gelu → per-row int8 requant.

    Composition of ops.int8's unfused pieces, kept bit-exact: the
    dequant/cast matches ``int8_matmul``'s rescale, the requant matches
    ``quant_act`` (same absmax/scale/clip math — change in lockstep).
    """
    h = jax.nn.gelu((y.astype(jnp.float32) * xs * ws).astype(out_dtype))
    xf = h.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s


def _dgr_kernel(y_ref, xs_ref, ws_ref, q_ref, s_ref, *, out_dtype):
    y = y_ref[...].astype(jnp.float32)                # (br, fp)
    xs = xs_ref[...][:, 0:1]                          # (br, 1)
    ws = ws_ref[...][0:1, :]                          # (1, fp)
    h = (y * xs * ws).astype(out_dtype)               # the GEMM's rounding
    # gelu in f32, rounded to out_dtype ONCE. Left in bf16, Mosaic rounds
    # after every elementwise op on chips without a bf16 VPU (v5e) and
    # drifts up to 3 int8 codes from XLA's unfused bf16 chain; this form
    # stays within 2 (chip_smoke.py bounds it), and is exact in f32.
    xf = jax.nn.gelu(h.astype(jnp.float32)).astype(out_dtype) \
        .astype(jnp.float32)
    # padded columns carry ws=0 → h=0 → gelu(0)=0: no effect on absmax
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    s = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q_ref[...] = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(s, s_ref.shape)


#: int32 accumulator bytes one grid step may hold: the kernel keeps the
#: block plus ~4 float32 temporaries of its size live, double-buffered
#: input included, inside Mosaic's scoped VMEM (16 MiB by default on v5e —
#: a fixed 256-row block is 16 MiB at F=16384 and fails to compile there)
_DGR_BLOCK_BYTES = 2 * 1024 * 1024


def _dgr_pallas(y: jax.Array, xs: jax.Array, ws: jax.Array, *, out_dtype,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    lead = y.shape[:-1]
    f = y.shape[-1]
    y2 = y.reshape(-1, f)
    r = y2.shape[0]
    fp = -(-f // _LANE) * _LANE
    # rows per step: whole rows (the per-row absmax needs them), in
    # multiples of the int8 output tile height, as many as the VMEM
    # budget allows — 128 at F=4096, 32 at F=16384
    fit = max(32, _DGR_BLOCK_BYTES // (fp * 4) // 32 * 32)
    block_rows = min(max(32, -(-max(r, 1) // 32) * 32), fit, 256)
    rp = -(-max(r, 1) // block_rows) * block_rows
    ypad = jnp.zeros((rp, fp), jnp.int32).at[:r, :f].set(y2)
    xspad = jnp.zeros((rp, _LANE), jnp.float32).at[:r, 0].set(
        xs.reshape(-1).astype(jnp.float32))
    wspad = jnp.zeros((8, fp), jnp.float32).at[0, :f].set(
        ws.astype(jnp.float32))
    grid = (rp // block_rows,)
    q, s = pl.pallas_call(
        functools.partial(_dgr_kernel, out_dtype=out_dtype),
        out_shape=(jax.ShapeDtypeStruct((rp, fp), jnp.int8),
                   jax.ShapeDtypeStruct((rp, _LANE), jnp.float32)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, fp), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0)),
                  pl.BlockSpec((8, fp), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((block_rows, fp), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0))),
        interpret=interpret,
    )(ypad, xspad, wspad)
    return (q[:r, :f].reshape(tuple(lead) + (f,)),
            s[:r, :1].reshape(tuple(lead) + (1,)))


def dequant_gelu_requant(y: jax.Array, xs: jax.Array, ws: jax.Array,
                         out_dtype=jnp.bfloat16, interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """Fused w8a8 MLP inner epilogue.

    ``y`` is the (..., F) int32 GEMM accumulator, ``xs`` the (..., 1)
    activation scales, ``ws`` the (F,) weight scales. Returns the
    requantized (..., F) int8 activations and their (..., 1) scales, so
    the second GEMM consumes int8 directly — no f32 round trip in HBM.
    """
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.dequant_gelu_requant", y.shape, y.dtype)
    return per_platform(
        functools.partial(_dgr_pallas, out_dtype=out_dtype,
                          interpret=interpret),
        functools.partial(dequant_gelu_requant_reference,
                          out_dtype=out_dtype),
        interpret, y, xs, ws)
