"""Dropless grouped expert GEMM, as a Pallas TPU kernel.

A routed layer's rows each pick ``k`` of ``E`` gated MLPs. At decode sizes
(tens of rows) a step is bound by the bytes of the experts that were hit,
so the kernel reads each hit expert's three matrices once and no others:
no gather that materialises a row's experts, no pass over experts nobody
picked, and no capacity: a row gets every expert it picked, however uneven
the picks are.

The wrapper turns the picks into *tiles* (``route_tiles``): an expert's
rows in groups of ``TILE_ROWS``, experts in order, so a tile belongs to
one expert and an expert nobody picked has none. The kernel's grid walks
(tile, slice of the hidden width); the tile's expert is a prefetched
scalar that the weights' block index reads, so Pallas streams expert
after expert through a two-deep pipeline. A tile's rows are gathered by a
one-hot product on the MXU (exact at ``highest``: a one-hot factor has no
low part), and its result is scattered back, weighted, the same way; the
output stays in VMEM for the whole walk. Tiles past the last real one
keep the last block index, so they move no bytes, and compute nothing.

Arithmetic is float32 with every product at ``highest``.

Where the computation is not placed on a TPU, or a width is not a multiple
of the 128 lanes, the dense form runs (``expert_mlp_reference``: a loop
over all experts, every row through each, weighted by the picks); it is
also what the tests hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import per_platform

_HI = jax.lax.Precision.HIGHEST
#: rows of one tile: an expert's group is walked in tiles of this many
#: rows. At 32 a decode step's group is always one tile, and the MXU's
#: time a tile is the weights' load, not the rows
TILE_ROWS = 32
#: most columns of the hidden width a grid step holds: three blocks of
#: (D, 512) float32, double-buffered, are 24 MB of VMEM at D = 2048
_HIDDEN_BLOCK = 512


def hidden_block(width: int) -> int:
    """Largest divisor of ``width`` that is a multiple of 128 and at most
    ``_HIDDEN_BLOCK``; 0 where there is none, and the dense form runs."""
    for b in range(min(_HIDDEN_BLOCK, width) // 128 * 128, 0, -128):
        if width % b == 0:
            return b
    return 0


def pick_weights(topi, topw, live, n_experts: int):
    """(R, E) float32: the weight row r gives expert e, 0 where it did not
    pick it or the row is not ``live`` (None: all are); and the same as a
    bool."""
    onehot = topi[:, :, None] == jnp.arange(n_experts, dtype=topi.dtype)
    if live is not None:
        onehot = onehot & live[:, None, None]
    weight = jnp.sum(jnp.where(onehot, topw[:, :, None], 0.0), axis=1)
    return weight, onehot.any(axis=1)


def max_tiles(rows: int, k: int, n_experts: int, tile: int = TILE_ROWS) -> int:
    """Most tiles ``rows`` rows of ``k`` picks can make: one an expert and
    one more for each full tile of picks."""
    return min(n_experts, rows * k) + (rows * k) // tile


def route_tiles(weight, member, n_max: int, tile: int = TILE_ROWS):
    """The kernel's work list from the picks ((R, E) each), ``n_max`` tiles
    long (``max_tiles``).

    Returns ``(tile_expert (T,), n_tiles (1,), sel (T, tile, R), comb (T, R,
    tile))``: tile t gathers ``sel[t] @ x`` (one-hot rows: the t-th group of
    ``tile`` rows that picked its expert, in row order) and scatters
    ``comb[t] @ y`` back (the transpose, times the rows' weights). Tiles
    past ``n_tiles`` select nothing and name the last real tile's expert,
    so their blocks are the ones already held."""
    e = member.shape[1]
    counts = member.sum(axis=0).astype(jnp.int32)              # (E,)
    tiles_e = -(-counts // tile)
    ends = jnp.cumsum(tiles_e)
    n_tiles = ends[-1]
    t = jnp.arange(n_max, dtype=jnp.int32)
    real = t < n_tiles
    expert = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1), e - 1).astype(jnp.int32)
    expert = jnp.where(real, expert, expert[jnp.maximum(n_tiles - 1, 0)])
    part = t - (ends - tiles_e)[expert]          # which tile of its group
    rank = jnp.cumsum(member, axis=0).astype(jnp.int32) - 1    # (R, E)
    want = part[:, None] * tile + jnp.arange(tile, dtype=jnp.int32)[None]
    sel = (member.T[expert][:, None, :] & real[:, None, None]
           & (rank.T[expert][:, None, :] == want[:, :, None]))  # (T, tile, R)
    sel = sel.astype(jnp.float32)
    comb = sel.transpose(0, 2, 1) * weight.T[expert][:, :, None]
    return expert, n_tiles.reshape(1).astype(jnp.int32), sel, comb


def _silu(x):
    return x * jax.nn.sigmoid(x)


def expert_mlp_reference(x, weight, w_gate, w_up, w_down):
    """Dense form: ``sum_e weight[:, e] * E_e(x)``, a loop over all experts,
    every row through each. x: (R, D); weight: (R, E), 0 where a row did
    not pick the expert; w_gate, w_up: (E, D, F); w_down: (E, F, D)."""
    def one(y, ew):
        wg, wu, wd, w = ew
        h = _silu(jnp.dot(x, wg, precision=_HI)) * jnp.dot(x, wu,
                                                           precision=_HI)
        return y + w[:, None] * jnp.dot(h, wd, precision=_HI), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w_gate, w_up, w_down, weight.T))
    return y


def _gemm_kernel(expert_ref, n_ref, x_ref, sel_ref, comb_ref, wg_ref, wu_ref,
                 wd_ref, out_ref, xt_scr, y_scr, *, n_blocks: int):
    del expert_ref  # read by the weights' block index
    t, f = pl.program_id(0), pl.program_id(1)
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)

    @pl.when((t == 0) & (f == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < n_ref[0])
    def _tile():
        @pl.when(f == 0)
        def _gather():
            xt_scr[...] = dot(sel_ref[0], x_ref[...])
            y_scr[...] = jnp.zeros_like(y_scr)

        xt = xt_scr[...]
        h = _silu(dot(xt, wg_ref[0])) * dot(xt, wu_ref[0])
        y_scr[...] += dot(h, wd_ref[0])

        @pl.when(f == n_blocks - 1)
        def _scatter():
            out_ref[...] += dot(comb_ref[0], y_scr[...])


def _gemm_pallas(x, weight, member, w_gate, w_up, w_down, *, k: int,
                 block: int, interpret: bool):
    r, d = x.shape
    e, _, width = w_gate.shape
    n_max = max_tiles(r, k, e)
    n_blocks = width // block
    expert, n_tiles, sel, comb = route_tiles(weight, member, n_max)

    def at_block(t, f, n_ref):
        # a tile past the last keeps the last block: nothing is fetched
        return jnp.where(t < n_ref[0], f, n_blocks - 1)

    whole = lambda t, f, *_: (0, 0)                          # noqa: E731
    tile3 = lambda t, f, *_: (t, 0, 0)                       # noqa: E731
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_max, n_blocks),
        in_specs=[
            pl.BlockSpec((r, d), whole),
            pl.BlockSpec((1, TILE_ROWS, r), tile3),
            pl.BlockSpec((1, r, TILE_ROWS), tile3),
            pl.BlockSpec((1, d, block), lambda t, f, ex, n: (
                ex[t], 0, at_block(t, f, n))),
            pl.BlockSpec((1, d, block), lambda t, f, ex, n: (
                ex[t], 0, at_block(t, f, n))),
            pl.BlockSpec((1, block, d), lambda t, f, ex, n: (
                ex[t], at_block(t, f, n), 0)),
        ],
        out_specs=pl.BlockSpec((r, d), whole),
        scratch_shapes=[pltpu.VMEM((TILE_ROWS, d), jnp.float32),
                        pltpu.VMEM((TILE_ROWS, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_blocks=n_blocks),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three weight blocks, double-buffered, and the small operands
            vmem_limit_bytes=6 * d * block * 4 + (24 << 20)),
        name="moe_expert_gemm",
        interpret=interpret,
    )(expert, n_tiles, x, sel, comb, w_gate, w_up, w_down)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def expert_gemm(x, weight, member, w_gate, w_up, w_down, *, k: int,
                interpret: bool = False):
    """``sum_e weight[:, e] * E_e(x)`` over the experts that ``member``
    marks, each a gated SiLU MLP ``(silu(x Wg) * x Wu) Wd``.

    x: (R, D) float32; weight, member: (R, E) from :func:`pick_weights`
    (a row marks at most ``k`` experts); w_gate, w_up: (E, D, F); w_down:
    (E, F, D). On a TPU the kernel reads the experts that were hit;
    elsewhere, and where D or F is no multiple of the 128 lanes or the
    weights are not float32, the dense form over all experts runs.
    ``interpret=True`` (tests) runs the kernel through the Pallas
    interpreter. Jitted, so that a step's unrolled layers trace the kernel
    once."""
    d, width = w_gate.shape[1:]
    block = hidden_block(width)
    if interpret and not block:
        block = width          # the interpreter takes any block
    dense = lambda x, weight, member, wg, wu, wd: (          # noqa: E731
        expert_mlp_reference(x, weight, wg, wu, wd))
    args = (x, weight, member, w_gate, w_up, w_down)
    if not block or (d % 128 and not interpret) \
            or w_gate.dtype != jnp.float32:
        return dense(*args)
    kernel = functools.partial(_gemm_pallas, k=k, block=block,
                               interpret=interpret)
    return per_platform(kernel, dense, interpret, *args)
