"""Pallas TPU kernels for the streaming preprocessing hot path.

The converter→transform→filter prologue is HBM-bandwidth-bound: read uint8
frames, normalize, cast to the MXU compute dtype. XLA fuses the elementwise
chain already (ops/fusion.py); these kernels exist for the cases XLA's
default pipeline doesn't schedule optimally and as the in-tree example of
the pallas path (/opt/skills/guides/pallas_guide.md patterns):

  * ``normalize_u8``     — uint8 → (x*scale + bias) in bf16/f32, tiled over
    (8,128)-aligned blocks in VMEM.
  * ``quantize_affine``  — float → uint8 affine quantization (the reverse
    boundary; reference quantized-model pipelines).

Both have jnp reference implementations: the entry points lower the
Mosaic kernel where the computation is placed on a TPU and the reference
on every other platform (``per_platform``); ``interpret=True`` runs the
Pallas body through the interpreter (tests).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...obs import profile as _profile
from . import per_platform


# --------------------------------------------------------------------------- #
# normalize_u8: y = x.astype(out_dtype) * scale + bias
# --------------------------------------------------------------------------- #

def _normalize_kernel(x_ref, o_ref, *, scale: float, bias: float, out_dtype):
    x = x_ref[...]
    if jnp.issubdtype(x.dtype, jnp.integer):
        # Mosaic has no direct uint8→float32 cast; hop through int32
        # (free on the VPU, verified on v5e). Float inputs must NOT take
        # this hop — it would truncate fractions.
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    o_ref[...] = (x * scale + bias).astype(out_dtype)


def normalize_u8_reference(x: jax.Array, scale: float, bias: float,
                           out_dtype=jnp.bfloat16) -> jax.Array:
    return (x.astype(jnp.float32) * scale + bias).astype(out_dtype)


def _normalize_pallas(x: jax.Array, *, scale: float, bias: float, out_dtype,
                      interpret: bool) -> jax.Array:
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lane = 128
    sublane = 32  # uint8 min tile height
    block = sublane * lane
    padded = -(-n // block) * block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    tiled = flat.reshape(-1, lane)
    rows = tiled.shape[0]
    block_rows = min(rows, 512)
    grid = (-(-rows // block_rows),)
    out = pl.pallas_call(
        functools.partial(_normalize_kernel, scale=float(scale),
                          bias=float(bias), out_dtype=out_dtype),
        out_shape=jax.ShapeDtypeStruct((rows, lane), out_dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
        interpret=interpret,
    )(tiled)
    return out.reshape(-1)[:n].reshape(orig_shape)


def normalize_u8(x: jax.Array, scale: float = 1.0 / 127.5,
                 bias: float = -1.0, out_dtype=jnp.bfloat16,
                 interpret: bool = False) -> jax.Array:
    """Normalize a uint8 tensor on the VPU via pallas; the jnp reference
    where the computation is not placed on a TPU."""
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.normalize_u8", x.shape, x.dtype)
    return per_platform(
        functools.partial(_normalize_pallas, scale=scale, bias=bias,
                          out_dtype=out_dtype, interpret=interpret),
        functools.partial(normalize_u8_reference, scale=scale, bias=bias,
                          out_dtype=out_dtype),
        interpret, x)


# --------------------------------------------------------------------------- #
# quantize_affine: q = clip(round(x / scale) + zero_point, 0, 255) as uint8
# --------------------------------------------------------------------------- #

def _quantize_kernel(x_ref, o_ref, *, inv_scale: float, zero_point: int):
    x = x_ref[...].astype(jnp.float32)
    q = jnp.round(x * inv_scale) + zero_point
    # float32→uint8 is unsupported on Mosaic; clamp then hop through int32
    o_ref[...] = jnp.clip(q, 0, 255).astype(jnp.int32).astype(jnp.uint8)


def quantize_affine_reference(x: jax.Array, scale: float,
                              zero_point: int = 0) -> jax.Array:
    q = jnp.round(x.astype(jnp.float32) / scale) + zero_point
    return jnp.clip(q, 0, 255).astype(jnp.uint8)


def _quantize_pallas(x: jax.Array, *, scale: float, zero_point: int,
                     interpret: bool) -> jax.Array:
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lane = 128
    block = 32 * lane  # uint8 output tiles are 32 rows high
    padded = -(-n // block) * block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    tiled = flat.reshape(-1, lane)
    rows = tiled.shape[0]
    block_rows = min(rows, 512)
    grid = (-(-rows // block_rows),)
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, inv_scale=1.0 / float(scale),
                          zero_point=int(zero_point)),
        out_shape=jax.ShapeDtypeStruct((rows, lane), jnp.uint8),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
        interpret=interpret,
    )(tiled)
    return out.reshape(-1)[:n].reshape(orig_shape)


def quantize_affine(x: jax.Array, scale: float, zero_point: int = 0,
                    interpret: bool = False) -> jax.Array:
    if _profile.KERNEL_HOOK is not None:  # trace-time kernel label
        _profile.KERNEL_HOOK("pallas.quantize_affine", x.shape, x.dtype)
    return per_platform(
        functools.partial(_quantize_pallas, scale=scale,
                          zero_point=zero_point, interpret=interpret),
        functools.partial(quantize_affine_reference, scale=scale,
                          zero_point=zero_point),
        interpret, x)
