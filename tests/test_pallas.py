"""Pallas kernel correctness (interpret mode on the CPU mesh; the same
kernels compile for TPU via pallas_call)."""

import numpy as np
import pytest

from nnstreamer_tpu.ops.pallas import preprocess as pp


class TestNormalize:
    def test_matches_reference(self):
        import jax.numpy as jnp

        x = np.random.default_rng(0).integers(0, 256, (2, 33, 47, 3)).astype(np.uint8)
        out = pp.normalize_u8(jnp.asarray(x), interpret=True, out_dtype=jnp.float32)
        ref = pp.normalize_u8_reference(jnp.asarray(x), 1 / 127.5, -1.0, jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
        assert out.shape == x.shape

    def test_nonaligned_sizes(self):
        import jax.numpy as jnp

        for shape in [(1,), (7, 13), (129,), (31, 127)]:
            x = np.ones(shape, np.uint8) * 200
            out = pp.normalize_u8(jnp.asarray(x), interpret=True,
                                  out_dtype=jnp.float32)
            np.testing.assert_allclose(np.asarray(out),
                                       (200 / 127.5 - 1.0) * np.ones(shape),
                                       rtol=1e-6)


class TestQuantize:
    def test_roundtrip(self):
        import jax.numpy as jnp

        x = np.random.default_rng(1).uniform(-1, 1, (16, 130)).astype(np.float32)
        q = pp.quantize_affine(jnp.asarray(x), scale=1 / 127.5, zero_point=128,
                               interpret=True)
        ref = pp.quantize_affine_reference(jnp.asarray(x), 1 / 127.5, 128)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(ref))
        assert np.asarray(q).dtype == np.uint8


class TestFlashAttention:
    """Blockwise causal attention kernel (ops/pallas/flash_attention.py)
    vs the dense reference, interpret mode on the CPU mesh."""

    @pytest.mark.parametrize("shape,causal", [
        ((1, 2, 64, 32), True),
        ((2, 1, 100, 16), True),     # non-block-multiple length
        ((1, 2, 64, 32), False),
        ((1, 1, 7, 8), True),        # shorter than one block
        ((1, 2, 100, 16), False),    # padded + full attention
    ])
    def test_matches_dense_reference(self, shape, causal):
        self._check(shape, causal, 32, 32)

    @pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (16, 4), (4, 16)])
    def test_unequal_blocks(self, bq, bk):
        """block_q != block_k with L=40 padded to 128: sub-128 requests
        resolve to divisors of the padded length, so the multi-block
        tiling (and the whole-k-block causal skip) really executes —
        trailing keys must not drop / output rows must not go
        unwritten."""
        from nnstreamer_tpu.ops.pallas.flash_attention import _pick_block

        # guard the guard: both picks must stay sub-128 multi-block
        assert _pick_block(128, bq) > 1 and _pick_block(128, bq) <= bq
        assert _pick_block(128, bk) > 1 and _pick_block(128, bk) <= bk
        self._check((1, 1, 40, 16), True, bq, bk)
        self._check((1, 1, 40, 16), False, bq, bk)

    def _check(self, shape, causal, bq, bk):
        from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention
        from nnstreamer_tpu.parallel.ring import reference_attention

        rng = np.random.default_rng(5)
        q, k, v = [rng.standard_normal(shape).astype(np.float32)
                   for _ in range(3)]
        out = np.asarray(flash_attention(q, k, v, causal=causal,
                                         block_q=bq, block_k=bk))
        ref = np.asarray(reference_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_lm_prefill_flash_equals_dense(self, monkeypatch):
        """NNS_LM_FLASH=1 swaps the prefill attention for the pallas
        kernel; logits and the emitted KV cache must match the dense
        path."""
        import jax

        from nnstreamer_tpu.models.causal_lm import init_causal_lm, lm_prefill

        params = init_causal_lm(jax.random.PRNGKey(0), vocab=64, d_model=32,
                                n_heads=4, n_layers=2, max_len=64)
        toks = np.asarray(
            np.random.default_rng(2).integers(0, 64, (2, 48)), np.int32)
        dense = lm_prefill(params, toks, n_heads=4, max_len=64)
        monkeypatch.setenv("NNS_LM_FLASH", "1")
        flash = lm_prefill(params, toks, n_heads=4, max_len=64)
        for a, b in zip(dense, flash):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_flash_head_dim_padding_numerics():
    """The real-TPU head-dim pad to 128 lanes must not change results
    (exercised in interpret mode via the test hook; sm_scale uses the
    TRUE head dim, not the padded one)."""
    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention
    from nnstreamer_tpu.parallel.ring import reference_attention

    rng = np.random.default_rng(11)
    q, k, v = [rng.standard_normal((1, 2, 48, 64)).astype(np.float32)
               for _ in range(3)]
    out = np.asarray(flash_attention(q, k, v, causal=True, block_q=16,
                                     block_k=16, _force_pad_d=True))
    assert out.shape == q.shape  # padded d columns sliced off
    ref = np.asarray(reference_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_flash_bf16_inputs_tolerance():
    """bf16 q/k/v: the flash precision model (bf16 softmax weights, f32
    accumulate) tracks the f32 oracle to ~1e-2 relative."""
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention
    from nnstreamer_tpu.parallel.ring import reference_attention

    rng = np.random.default_rng(13)
    qf, kf, vf = [rng.standard_normal((1, 2, 64, 32)).astype(np.float32)
                  for _ in range(3)]
    out = np.asarray(flash_attention(
        jnp.asarray(qf, jnp.bfloat16), jnp.asarray(kf, jnp.bfloat16),
        jnp.asarray(vf, jnp.bfloat16), causal=True,
        block_q=16, block_k=16)).astype(np.float32)
    ref = np.asarray(reference_attention(qf, kf, vf, causal=True))
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=3e-2)


def _flash_residuals(q, k, v):
    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, return_residuals=True)


def _flash(q, k, v):
    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v)


@pytest.mark.parametrize("fn,n_args,shape,dtype", [
    (_flash, 3, (8, 32, 2048, 128), "bfloat16"),  # round-5 roofline shape
    (_flash, 3, (1, 16, 8192, 64), "bfloat16"),   # long context, padded d
    (_flash_residuals, 3, (1, 16, 8192, 64), "bfloat16"),
    (pp.normalize_u8, 1, (1, 224, 224, 3), "uint8"),
    (lambda x: pp.quantize_affine(x, 1 / 127.5, 128), 1,
     (1, 224, 224, 3), "float32"),
])
def test_kernel_lowers_for_tpu(fn, n_args, shape, dtype):
    """Lower for the TPU platform from this CPU host at the production
    shapes: catches a Pallas-TPU lowering refusal without a chip, and
    pins that the TPU program carries the Mosaic call (Mosaic's own
    passes only run on the chip: chip_smoke.py)."""
    import jax

    spec = jax.ShapeDtypeStruct(shape, dtype)
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *[spec] * n_args).mlir_module()
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------- #
# decode attention (ops/pallas/decode_attention.py): the kernel through the
# Pallas interpreter against the dense masked form, at toy sizes
# --------------------------------------------------------------------------- #

_DA_MAX_LEN, _DA_BLOCK = 64, 16   # four blocks a stream
_DA_GRAIN = 8                     # two sub-tiles a stream's last block


def _decode_case(pos, active, layer_axis=1, li=1, n_layers=2, heads=2,
                 max_len=_DA_MAX_LEN, hd=8, seed=0):
    import jax
    import jax.numpy as jnp

    b = len(pos)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (b, n_layers, heads, max_len, hd) if layer_axis == 1 \
        else (n_layers, b, heads, max_len, hd)
    kc, vc = (jax.random.normal(k, shape) for k in ks[:2])
    q, kn, vn = (jax.random.normal(k, (b, heads, 1, hd)) for k in ks[2:])
    return (q, kn, vn, kc, vc, jnp.int32(li), jnp.asarray(pos, jnp.int32),
            None if active is None else jnp.asarray(active, bool))


def _assert_kernel_is_dense(args, layer_axis=1, block=_DA_BLOCK,
                            grain=_DA_GRAIN):
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    want = da.window_attention_reference(*args, layer_axis=layer_axis)
    got = da._decode_pallas(*args, layer_axis=layer_axis, block=block,
                            grain=grain, interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-6, atol=2e-6)
    # the stores: the new rows written, nothing else touched
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    return got


@pytest.mark.parametrize("pos", [
    0,                                  # an empty store: the new row alone
    1,
    _DA_BLOCK - 1, _DA_BLOCK, _DA_BLOCK + 1,    # a block's edge
    3 * _DA_BLOCK + 5,
    _DA_MAX_LEN - 1,                    # the last row a stream may write
    # the tail's edges: one sub-tile short of, on and past a grain
    _DA_GRAIN - 1, _DA_GRAIN, _DA_GRAIN + 1,
    _DA_BLOCK + _DA_GRAIN - 1, _DA_BLOCK + _DA_GRAIN,
    _DA_BLOCK + _DA_GRAIN + 1, 2 * _DA_BLOCK - 1,
])
def test_decode_attention_lengths(pos):
    """Every stream at one length: ``pos`` store rows and the new one."""
    _assert_kernel_is_dense(_decode_case([pos] * 3, [True] * 3))


@pytest.mark.parametrize("pos,active", [
    ([1, 17, 63, 40], [True, True, True, True]),       # mixed lengths
    ([33, 5, 16, 47], [True, False, True, True]),      # a slot of length 0
    ([9, 9, 9, 9], [False, True, False, False]),       # gaps on both sides
    ([7, 30, 2, 11], [False, False, False, False]),    # nobody home
    # tails that end in different sub-tiles of different blocks
    ([9, 23, 40, 62], [True, True, True, True]),
    ([31, 8, 47, 17], [True, True, False, True]),
])
def test_decode_attention_mixed_slots(pos, active):
    args = _decode_case(pos, active)
    o, kc, vc = _assert_kernel_is_dense(args)
    vn = np.asarray(args[2])
    for b, act in enumerate(active):
        if not act:
            # a stream without a request: no row written, its own value
            # row handed back
            np.testing.assert_array_equal(np.asarray(o)[b], vn[b])
            np.testing.assert_array_equal(np.asarray(kc)[b],
                                          np.asarray(args[3])[b])


@pytest.mark.parametrize("poison_from", ["grain", "pos"])
def test_decode_attention_tail_reads_no_row_past_its_grain(poison_from):
    """Every store row at or past a stream's position rounded up to the
    grain is NaN (or at or past the position itself, rows the tail does
    fetch): the kernel's output is the dense form's on the clean store, so
    no such row is read, or none reaches the output."""
    import jax.numpy as jnp

    pos = [5, 16, 27, 41]
    args = _decode_case(pos, [True] * 4)
    want = _assert_kernel_is_dense(args)
    g = _DA_GRAIN
    ends = [-(-p // g) * g if poison_from == "grain" else p + 1
            for p in pos]
    rows = np.arange(_DA_MAX_LEN)
    dirty = jnp.asarray(rows[None, :] >= np.asarray(ends)[:, None])
    dirty = dirty[:, None, None, :, None]
    kc, vc = (jnp.where(dirty, jnp.nan, c) for c in args[3:5])
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    got = da._decode_pallas(*args[:3], kc, vc, *args[5:], layer_axis=1,
                            block=_DA_BLOCK, grain=g, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("layer_axis,li", [(0, 0), (0, 1), (1, 0)])
def test_decode_attention_layouts(layer_axis, li):
    """Streams in step (layers first, a scalar position, no active set)
    and a store a slot (slots first): the same kernel finds its rows."""
    import jax.numpy as jnp

    if layer_axis == 0:
        args = _decode_case([21] * 3, None, layer_axis=0, li=li)
        args = args[:6] + (jnp.int32(21), None)
    else:
        args = _decode_case([21, 4, 50], [True] * 3, li=li)
    _assert_kernel_is_dense(args, layer_axis=layer_axis)


def test_decode_attention_block_choice_and_fallback():
    """The block divides ``max_len``; where no multiple of 8 does, the head
    size is not whole lanes or the store is not float32, the dense form
    runs whatever the platform."""
    import functools

    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.pallas import decode_attention as da

    assert da.kv_block(2048) == 256 and da.kv_block(64) == 64
    assert da.kv_block(96) == 96 and da.kv_block(640) == 160
    assert da.kv_block(10) == 0
    assert da.read_grain(2048) == 32 and da.read_grain(640) == 32
    assert da.read_grain(64) == 32 and da.read_grain(16) == 16
    assert da.read_grain(10) == 0
    # whole blocks, the last block to the tail's grain
    g = da.read_grain(2048)
    assert da.rows_read(0, 2048, g) == 0 and da.rows_read(1, 2048, g) == 32
    assert da.rows_read(32, 2048, g) == 32
    assert da.rows_read(33, 2048, g) == 64
    assert da.rows_read(256, 2048, g) == 256
    assert da.rows_read(257, 2048, g) == 288
    assert da.rows_read(2048, 2048, g) == 2048
    assert da.rows_read(9, 10, da.read_grain(10)) == 10

    fn = functools.partial(da.decode_attention, layer_axis=1,
                           interpret=True)

    def takes_kernel(args):
        return "pallas_call" in str(jax.make_jaxpr(fn)(*args))

    lanes = _decode_case([3, 9], [True, True], hd=128)
    assert takes_kernel(lanes)
    want = da.window_attention_reference(*lanes, layer_axis=1)
    np.testing.assert_allclose(np.asarray(fn(*lanes)[0]),
                               np.asarray(want[0]), rtol=2e-6, atol=2e-6)
    assert not takes_kernel(_decode_case([3, 9], [True, True], hd=64))
    assert not takes_kernel(
        _decode_case([3, 9], [True, True], hd=128, max_len=10))
    half = tuple(a.astype(jnp.bfloat16) if i in (3, 4) else a
                 for i, a in enumerate(lanes))
    assert not takes_kernel(half)
    short = _decode_case([3, 9], [True, True], max_len=10)
    got = fn(*short)
    want = da.window_attention_reference(*short, layer_axis=1)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_decode_attention_lowers_for_tpu():
    """The production shape (8 slots, 24 layers x 16 heads, 2048 x 128,
    float32) lowers for the TPU platform with the Mosaic call in it and
    the stores aliased in and out."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.pallas import decode_attention as da

    s, layers, h, m, hd = 8, 24, 16, 2048, 128
    row = jax.ShapeDtypeStruct((s, h, 1, hd), jnp.float32)
    store = jax.ShapeDtypeStruct((s, layers, h, m, hd), jnp.float32)

    def fn(q, kn, vn, kc, vc, li, pos, active):
        return da.decode_attention(q, kn, vn, kc, vc, li, pos, active,
                                   layer_axis=1)

    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        row, row, row, store, store, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((s,), jnp.int32),
        jax.ShapeDtypeStruct((s,), jnp.bool_)).mlir_module()
    assert "tpu_custom_call" in text
    assert "output_operand_alias" in text


def test_grouped_decode_attention_lowers_for_tpu():
    """Qwen3-Next's full layers (64 slots, 2 layers, 16 query heads over 2
    K/V heads of 256, 2048 positions, float32) lower for the TPU platform
    to the Mosaic call named ``decode_attention``, with the stores aliased
    in and out."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.pallas import decode_attention as da

    s, layers, hq, h, m, hd = 64, 2, 16, 2, 2048, 256
    row = jax.ShapeDtypeStruct((s, h, 1, hd), jnp.float32)
    store = jax.ShapeDtypeStruct((s, layers, h, m, hd), jnp.float32)

    def fn(q, kn, vn, kc, vc, li, pos, active):
        return da.decode_attention(q, kn, vn, kc, vc, li, pos, active,
                                   layer_axis=1)

    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        jax.ShapeDtypeStruct((s, hq, 1, hd), jnp.float32), row, row, store,
        store, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((s,), jnp.int32),
        jax.ShapeDtypeStruct((s,), jnp.bool_)).mlir_module()
    assert "tpu_custom_call" in text
    assert 'kernel_name = "decode_attention"' in text
    for out, operand in ((1, 9), (2, 10)):
        assert (f"output_tuple_indices = [{out}], operand_index = {operand},"
                in text)
