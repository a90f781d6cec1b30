"""Blockwise (flash) causal attention as a Pallas TPU kernel.

The transformer prefill/decode hot op. The dense path materialises the
(L, L) score matrix in HBM; this kernel streams K/V blocks through VMEM
with the online-softmax recurrence, so memory is O(Bq·Bk) per core and
the matmuls stay on the MXU (jnp.dot with preferred_element_type=f32).

Grid layout: ``(batch·heads, q_blocks, k_blocks)`` — the k dimension is
an ACCUMULATION axis: scratch (o, m, l) lives in VMEM across the k steps
(TPU grids execute sequentially over the last axis), initialised at
``ki == 0`` and finalised into the output block at the last step.
Causal masking is two-level: whole k-blocks strictly above the diagonal
are skipped via ``pl.when``, the diagonal block applies the per-element
mask.

Where the computation is not placed on a TPU (tests, CPU mesh) the same
kernel runs through the Pallas interpreter; the choice is made per
lowering platform (``jax.lax.platform_dependent``).

Reference equivalent: the reference has no attention kernels (its models
are CNNs served by vendor runtimes); this is TPU-first scope from
SURVEY §7 (long-context machinery) — the single-device complement of
parallel/ring.py's cross-chip ring.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import tune as _tune
from ...obs import profile as _profile

_NEG_INF = -1e30  # mask value; finite so (m - m) stays NaN-free


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, n_kblocks: int, causal: bool,
                  true_len: int, sm_scale: float, normalize: bool = True):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # whole block strictly above the causal diagonal: contributes nothing
    run = jnp.logical_or(not causal,
                         ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0]                    # (block_q, d)
        k = k_ref[0]                    # (block_k, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = s * np.float32(sm_scale)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = cols < true_len  # padded keys must never win the softmax
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, rows >= cols)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:]               # (block_q, 1)
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        if normalize:
            denom = jnp.maximum(l_ref[:], 1e-30)
            o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        else:  # residual mode: the UNNORMALIZED accumulator is the output
            o_ref[0] = acc_ref[:].astype(o_ref.dtype)


def _flash_kernel_residual(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                           acc_ref, m_ref, l_ref, *, block_q: int,
                           block_k: int, n_kblocks: int, causal: bool,
                           true_len: int, sm_scale: float):
    """Same online-softmax recurrence, but emits the UNNORMALIZED
    accumulator plus the per-row softmax residuals (rowmax m, normalizer
    l) so partial attentions over disjoint key sets merge exactly (ring
    attention steps) without a divide/re-multiply round trip."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  block_q=block_q, block_k=block_k, n_kblocks=n_kblocks,
                  causal=causal, true_len=true_len, sm_scale=sm_scale,
                  normalize=False)
    ki = pl.program_id(2)

    @pl.when(ki == n_kblocks - 1)
    def _emit_residuals():
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _pick_block(lp: int, want: int) -> int:
    """Largest exact divisor of ``lp`` (a multiple of 128) that is
    <= ``want``, preferring lane-aligned multiples of 128. Keeping
    blocks as divisors of the padded length means no lcm re-padding —
    a 640-long sequence gets 128-wide blocks, not a blow-up to
    lcm(512, 640). Requests below 128 (tests, ring steps over short
    shards) get the largest plain divisor <= the request, so explicit
    small blocks still exercise multi-block tiling."""
    m = lp // 128
    best = 0
    for d in range(1, m + 1):
        if m % d == 0 and d * 128 <= want:
            best = d * 128
    if best:
        return best
    for d in range(1, min(want, lp) + 1):
        if lp % d == 0:
            best = d
    return best or 1


#: the candidate grid the autotuner sweeps/ranks — exactly the round-5
#: hand-sweep grid, so a tuner pick can never be worse than the best
#: hand-swept point on the same hardware
_TUNE_GRID = ((128, 128), (256, 256), (512, 512), (512, 1024),
              (1024, 1024))
#: hand-swept at round 5 — what every call gets when the tuner is off
#: or has nothing better
_DEFAULT_BLOCKS = (512, 1024)


def _block_features(b: int, h: int, L: int, d: int, itemsize: int):
    """Per-candidate (flops, bytes) for the cost model: FLOPs are
    block-independent; HBM traffic is not — each q block streams the
    whole K/V once, so K/V re-reads scale with Lp/block_q, and q/o
    re-reads with Lp/block_k staying resident. A coarse roofline, but
    it orders the grid the same way the hand sweep did."""
    Lp = -(-L // 128) * 128
    flops = 4.0 * b * h * Lp * Lp * d  # qk^T + pv, causal ~x0.5 folds
    # into the constant and cancels in ranking

    def features(cand):
        bq, bk = cand
        nq = max(Lp // max(min(bq, Lp), 1), 1)
        kv_traffic = 2.0 * b * h * nq * Lp * d * itemsize
        qo_traffic = 2.0 * b * h * Lp * d * itemsize
        return flops, kv_traffic + qo_traffic

    return features


def _tuned_blocks(q, k, v, causal: bool, interpret: Optional[bool]):
    """Resolve (block_q, block_k) through the autotuner. Store/model
    hits are free; with neither, a bounded measured sweep times the
    candidate grid on throwaway arrays of the caller's shape — safe
    even while tracing, because the sweep inputs are concrete (jax
    executes them eagerly) and the recursive calls pass explicit
    blocks, which never re-enter the tuner."""
    tn = _tune.TUNE_HOOK
    if tn is None:
        return _DEFAULT_BLOCKS
    b, h, L, d = q.shape
    sig = _tune.shape_sig(("b", b), ("h", h), ("l", L), ("d", d),
                          ("c", int(causal)))
    dev = "interpret" if interpret else _tune.device_kind()
    dt = q.dtype

    def measure(cand):
        bq, bk = cand
        qq = jnp.ones((b, h, L, d), dt)
        kk = jnp.ones((b, h, L, d), dt)
        vv = jnp.ones((b, h, L, d), dt)
        flash_attention(qq, kk, vv, causal=causal, block_q=bq,
                        block_k=bk,
                        interpret=interpret).block_until_ready()  # warm
        t0 = time.perf_counter()
        flash_attention(qq, kk, vv, causal=causal, block_q=bq,
                        block_k=bk,
                        interpret=interpret).block_until_ready()
        return time.perf_counter() - t0

    cand = tn.pick("flash_blocks", dev, "pallas.flash_attention", sig,
                   candidates=_TUNE_GRID, default=_DEFAULT_BLOCKS,
                   measure=measure,
                   features=_block_features(b, h, L, d, dt.itemsize))
    try:
        bq, bk = cand  # store round-trips tuples as lists
        return int(bq), int(bk)
    except (TypeError, ValueError):
        return _DEFAULT_BLOCKS


def _flash_pallas(q, k, v, *, causal: bool, block_q: int, block_k: int,
                  return_residuals: bool, pad_d: bool, interpret: bool):
    b, h, L, d_orig = q.shape
    sm_scale = 1.0 / float(np.sqrt(d_orig))  # from the TRUE head dim
    d = d_orig
    if pad_d and d % 128:
        # real-TPU lanes are 128-wide: zero-pad the head dim (zero q/k
        # columns add nothing to the scores; zero v columns are sliced
        # off at return). sm_scale above already uses the true d.
        dpad = -(-d // 128) * 128 - d
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        d = q.shape[-1]
    # pad the sequence up to a lane-tile multiple, then pick blocks as
    # exact divisors of the padded length (<= the requested sizes): both
    # blocks always tile Lp exactly, so no second lcm padding pass
    Lp = -(-L // 128) * 128
    bq = _pick_block(Lp, min(block_q, Lp))
    bk = _pick_block(Lp, min(block_k, Lp))
    if Lp != L:
        pad = Lp - L
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_q = Lp // bq
    n_k = Lp // bk
    assert n_q * bq == Lp and n_k * bk == Lp
    bh = b * h
    qf = q.reshape(bh, Lp, d)
    kf = k.reshape(bh, Lp, d)
    vf = v.reshape(bh, Lp, d)

    kfn = _flash_kernel_residual if return_residuals else _flash_kernel
    kernel = functools.partial(
        kfn, block_q=bq, block_k=bk, n_kblocks=n_k, causal=causal,
        true_len=L, sm_scale=sm_scale)
    o_spec = pl.BlockSpec((1, bq, d), lambda s, i, j: (s, i, 0))
    r_spec = pl.BlockSpec((1, bq, 1), lambda s, i, j: (s, i, 0))
    o_shape = jax.ShapeDtypeStruct(
        (bh, Lp, d), jnp.float32 if return_residuals else q.dtype)
    r_shape = jax.ShapeDtypeStruct((bh, Lp, 1), jnp.float32)
    result = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda s, i, j: (s, i, 0)),
            pl.BlockSpec((1, bk, d), lambda s, i, j: (s, j, 0)),
            pl.BlockSpec((1, bk, d), lambda s, i, j: (s, j, 0)),
        ],
        out_specs=[o_spec, r_spec, r_spec] if return_residuals else o_spec,
        out_shape=[o_shape, r_shape, r_shape] if return_residuals
        else o_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        # batch·heads and q-blocks are independent; only the k axis is an
        # accumulation (scratch carries across it) and must stay ordered
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    if return_residuals:
        acc, m_out, l_out = result
        return (acc.reshape(b, h, Lp, d)[:, :, :L, :d_orig],
                m_out.reshape(b, h, Lp)[:, :, :L],
                l_out.reshape(b, h, Lp)[:, :, :L])
    return result.reshape(b, h, Lp, d)[:, :, :L, :d_orig]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_residuals: bool = False,
                    _force_pad_d: bool = False):
    """Causal (or full) attention over ``(B, H, L, D)`` tensors.

    Sequence length is padded up to a block multiple internally (padded
    keys are masked via an explicit length mask), and on real TPUs a
    head dim that is not a multiple of the 128-wide lanes is zero-padded
    internally too (score-neutral; padded v columns sliced off, softmax
    scale from the true head dim) — callers never pad anything.

    ``interpret=None`` (the default) compiles the kernel with Mosaic
    where the computation is placed on a TPU and runs it through the
    Pallas interpreter on every other platform; a bool forces one.

    ``block_q``/``block_k`` default to the round-5 hand-swept 512/1024 —
    unless the autotuner hook is installed, in which case unset blocks
    resolve through its store/model/sweep (docs/tuning.md). Explicit
    values always win and never consult the tuner.

    Precision model: scores and the output accumulate in f32; the
    softmax weights are rounded to v's dtype before the PV matmul (the
    standard flash configuration). With bf16 inputs this differs from a
    full-f32 dense computation by ~1e-2 relative.
    """
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.flash_attention", q.shape, q.dtype)
    if block_q is None or block_k is None:
        tq, tk = _tuned_blocks(q, k, v, causal, interpret)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    run = functools.partial(
        _flash_pallas, causal=causal, block_q=block_q, block_k=block_k,
        return_residuals=return_residuals)
    if interpret is not None:
        return run(q, k, v, pad_d=_force_pad_d or not interpret,
                   interpret=interpret)
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=functools.partial(run, pad_d=True, interpret=False),
        default=functools.partial(run, pad_d=_force_pad_d, interpret=True))
