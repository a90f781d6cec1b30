"""Sequence/context parallelism: ring attention + all-to-all (Ulysses-style)
attention over a device mesh.

The reference has no sequence-axis scaling beyond temporal windowing
(SURVEY §5); for long-sequence streaming workloads (video token streams,
audio, transformer filters) this module makes context parallelism a
first-class capability:

  * ``ring_attention`` — each device holds a sequence shard of Q/K/V; K/V
    blocks rotate around the ring via ``jax.lax.ppermute`` (ICI
    neighbor-to-neighbor, bandwidth-optimal) while a flash-style online
    softmax accumulates exact attention. Memory per device is O(L/N · L/N),
    enabling sequences N× longer than one chip could hold.
  * ``a2a_attention`` — Ulysses-style: ``all_to_all`` re-shards sequence →
    heads, each device runs full-sequence attention for its head subset,
    then re-shards back. One collective pair instead of N ring steps;
    preferred when heads ≥ devices and full L×L fits per head.

Both are exact (match single-device attention to float tolerance) and
jit/shard_map-compatible; tests validate on the virtual 8-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (specs are
    managed explicitly)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _online_block(q, k, v, m_prev, l_prev, o_prev, mask=None):
    """One flash-attention accumulation step against a K/V block."""
    d = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(d).astype(q.dtype)
    if mask is not None:
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-shard body (runs under shard_map): q,k,v are the local sequence
    shard [batch, heads, l_local, d]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    l_local = q.shape[-2]

    m0 = jnp.full(q.shape[:-1], jnp.finfo(jnp.float32).min, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)
    qf = q.astype(jnp.float32)

    def step(i, carry):
        m, l, o, kk, vv = carry
        # kv block currently held originated at shard (my_idx + i) % N
        src = (my_idx + i) % axis_size
        mask = None
        if causal:
            q_pos = my_idx * l_local + jnp.arange(l_local)
            k_pos = src * l_local + jnp.arange(l_local)
            mask = q_pos[:, None] >= k_pos[None, :]
        m, l, o = _online_block(qf, kk.astype(jnp.float32),
                                vv.astype(jnp.float32), m, l, o, mask)
        # rotate k/v to the next ring neighbor
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return m, l, o, kk, vv

    m, l, o, _, _ = jax.lax.fori_loop(0, axis_size, step, (m0, l0, o0, k, v))
    return (o / l[..., None]).astype(q.dtype)


def _ring_flash_local(q, k, v, axis_name: str, causal: bool,
                      block_q: int, block_k: int):
    """Per-shard ring body where each shard-pair partial runs through the
    blockwise pallas kernel (ops/pallas/flash_attention.py) instead of
    materializing the (l_local, l_local) score matrix — the long-context
    composition: ring over chips × flash within a chip. Partials merge
    exactly via their softmax residuals (m, l)."""
    from ..ops.pallas.flash_attention import _NEG_INF, flash_attention

    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    # sentinel MUST match the kernel's so skip-branch partials underflow
    # to zero contribution in the merge
    m0 = jnp.full(q.shape[:-1], _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)  # o·l (unnormalized)

    def partial_attn(is_causal):
        def run(kk, vv):
            # residual mode returns the UNNORMALIZED accumulator; inputs
            # keep their dtype. NOTE the flash precision model: softmax
            # weights round to v.dtype before the PV matmul (f32
            # accumulate), so with bf16 inputs this path tracks the
            # flash kernel's numerics, not plain ring_attention's
            # full-f32 ones (~1e-2 relative with bf16)
            return flash_attention(q, kk, vv, causal=is_causal,
                                   block_q=block_q, block_k=block_k,
                                   return_residuals=True)

        return run

    def partial_skip(kk, vv):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.full(q.shape[:-1], _NEG_INF, jnp.float32),
                jnp.zeros(q.shape[:-1], jnp.float32))

    def step(i, carry):
        m, l, acc, kk, vv = carry
        src = (my_idx + i) % axis_size
        if causal:
            # src < my: every key precedes every query (full);
            # src == my: aligned causal; src > my: fully masked
            branch = jnp.where(src < my_idx, 0,
                               jnp.where(src == my_idx, 1, 2))
            acc_i, m_i, l_i = jax.lax.switch(
                branch,
                [partial_attn(False), partial_attn(True), partial_skip],
                kk, vv)
        else:
            acc_i, m_i, l_i = partial_attn(False)(kk, vv)
        # exact merge of two attention partials over disjoint key sets
        m_new = jnp.maximum(m, m_i)
        a_old = jnp.exp(m - m_new)
        a_new = jnp.exp(m_i - m_new)
        l = l * a_old + l_i * a_new
        acc = acc * a_old[..., None] + acc_i * a_new[..., None]
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return m_new, l, acc, kk, vv

    m, l, acc, _, _ = jax.lax.fori_loop(
        0, axis_size, step, (m0, l0, acc0, k, v))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         mesh: Mesh, axis_name: str = "sp",
                         causal: bool = False, block_q: int = 128,
                         block_k: int = 128) -> jax.Array:
    """Ring attention with the pallas flash kernel per shard pair: memory
    per device is O(block_q·block_k) instead of O((L/N)²) — the intended
    configuration for genuinely long contexts."""
    spec = P(None, None, axis_name, None)
    fn = _shard_map(
        functools.partial(_ring_flash_local, axis_name=axis_name,
                          causal=causal, block_q=block_q, block_k=block_k),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis_name: str = "sp", causal: bool = False) -> jax.Array:
    """Exact attention over sequence shards on ``mesh[axis_name]``.

    q/k/v: [batch, heads, seq, head_dim] (global views; seq must divide by
    the axis size). Returns same-shape output, sequence-sharded."""
    spec = P(None, None, axis_name, None)
    fn = _shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _a2a_attention_local(q, k, v, axis_name: str, flash: bool = False):
    """Per-shard body: seq-sharded in, swap to head-sharded, attend, swap
    back. Requires heads % axis_size == 0. With ``flash`` the per-head
    full-sequence attention runs through the blockwise pallas kernel
    instead of materializing the (L, L) score matrix."""
    # [b, H, l_local, d] → all_to_all over heads: [b, H/N, L, d]
    qh = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    if flash:
        from ..ops.pallas.flash_attention import flash_attention

        oh = flash_attention(qh.astype(jnp.float32),
                             kh.astype(jnp.float32),
                             vh.astype(jnp.float32), causal=False)
    else:
        d = qh.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) / jnp.sqrt(d)
        p = jax.nn.softmax(s, axis=-1)
        oh = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    # back: heads gathered, sequence re-sharded
    o = jax.lax.all_to_all(oh.astype(q.dtype), axis_name, split_axis=2,
                           concat_axis=1, tiled=True)
    return o


def a2a_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                  axis_name: str = "sp", flash: bool = False) -> jax.Array:
    """Ulysses-style sequence-parallel attention (all_to_all re-sharding);
    ``flash=True`` runs each head subset through the pallas kernel."""
    n = mesh.shape[axis_name]
    if q.shape[1] % n != 0:
        raise ValueError(f"heads {q.shape[1]} not divisible by "
                         f"{axis_name} axis size {n}")
    spec = P(None, None, axis_name, None)
    fn = _shard_map(functools.partial(_a2a_attention_local,
                                      axis_name=axis_name, flash=flash),
                    mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False) -> jax.Array:
    """Single-device exact attention (correctness oracle)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(d)
    if causal:
        L = q.shape[-2]
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def sp_attention_fn(mode: str, mesh: Mesh, axis_name: str = "sp",
                    causal: bool = False):
    """``(q, k, v) -> o`` attention callable for the requested
    sequence-parallel mode — the one dispatch point model factories use
    (stream_transformer.make_sp_apply, moe_transformer.make_sp_ep_infer)."""
    if mode == "ring":
        return lambda q, k, v: ring_attention(q, k, v, mesh, axis_name,
                                              causal=causal)
    if mode == "ring-flash":
        return lambda q, k, v: ring_flash_attention(
            q, k, v, mesh, axis_name, causal=causal)
    if mode in ("a2a", "ulysses", "a2a-flash", "ulysses-flash"):
        if causal:
            raise ValueError("a2a/ulysses attention has no causal mode")
        use_flash = mode.endswith("-flash")
        return lambda q, k, v: a2a_attention(q, k, v, mesh, axis_name,
                                             flash=use_flash)
    raise ValueError(f"unknown sp mode {mode!r}")
