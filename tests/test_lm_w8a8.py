"""w8a8 int8 serving path (ops/int8.py + causal_lm.quantize_lm_params).

The reference serves quantized models through TFLite's int8 kernels
(ext/nnstreamer/tensor_filter/tensor_filter_tensorflow_lite.cc with the
mobilenet_*_quant.tflite test models); the TPU-idiomatic transformer
equivalent is dynamic-activation int8 GEMMs on the MXU's double-rate
path. Three contracts pinned here:

* the quantize/dot/rescale math is exactly the documented scheme
  (numpy integer reference, bit-level);
* quantized logits track the float model (bounded drift);
* the family's exactness-BETWEEN-FORMS contract survives quantization:
  int32 accumulation has no contraction-order drift, so prefill+decode,
  verify windows, vmapped slots, and the full forward agree at float
  roundoff — measured ~1e-7, the same level as the float paths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.ops import int8 as i8

V, D, H, L, T = 64, 64, 4, 2, 16


@pytest.fixture(scope="module")
def params():
    return causal_lm.init_causal_lm(jax.random.PRNGKey(0), V, D, H, L, T)


@pytest.fixture(scope="module")
def qparams(params):
    return causal_lm.quantize_lm_params(params)


def test_int8_matmul_matches_integer_reference():
    """The documented scheme, replayed in numpy int64: per-output-channel
    weight grid, per-row dynamic activation grid, exact int product,
    outer-product rescale."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)

    y = np.asarray(i8.int8_matmul(jnp.asarray(x), i8.quantize_weight(w)))

    wa = np.max(np.abs(w), axis=0)
    ws = np.where(wa == 0, 1.0, wa / 127.0)
    wq = np.clip(np.round(w / ws), -127, 127).astype(np.int64)
    xa = np.max(np.abs(x), axis=1, keepdims=True)
    xs = np.where(xa == 0, 1.0, xa / 127.0)
    xq = np.clip(np.round(x / xs), -127, 127).astype(np.int64)
    ref = (xq @ wq).astype(np.float32) * xs * ws
    np.testing.assert_allclose(y, ref, rtol=1e-6, atol=1e-7)


def test_quantize_weight_layer_stack_slices():
    """A scanned (L, K, N) stack quantizes to per-layer grids — each
    layer's slice must equal quantizing that layer alone."""
    rng = np.random.default_rng(1)
    w = rng.normal(size=(3, 8, 4)).astype(np.float32)
    stacked = i8.quantize_weight(w)
    for layer in range(3):
        alone = i8.quantize_weight(w[layer])
        np.testing.assert_array_equal(
            np.asarray(stacked[i8.W8A8_TAG][layer]),
            np.asarray(alone[i8.W8A8_TAG]))
        np.testing.assert_allclose(np.asarray(stacked["s"][layer]),
                                   np.asarray(alone["s"]))


def test_zero_rows_and_channels_are_safe():
    x = jnp.zeros((2, 8), jnp.float32)
    w = np.zeros((8, 4), np.float32)
    w[:, 0] = 1.0
    y = np.asarray(i8.int8_matmul(x, i8.quantize_weight(w)))
    assert np.isfinite(y).all() and (y == 0).all()


def test_quantized_logits_track_float(params, qparams):
    """Bounded drift vs the float model: dynamic per-token activation
    grids keep logits within a few percent (measured max ~2.6% of the
    logit scale on these dims)."""
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, V, (2, 10)).astype(np.int32))
    lf = np.asarray(causal_lm.lm_forward(params, toks, H))
    lq = np.asarray(causal_lm.lm_forward(qparams, toks, H))
    scale = np.abs(lf).max()
    assert np.abs(lq - lf).max() < 0.06 * scale
    cos = (lf * lq).sum(-1) / (
        np.linalg.norm(lf, axis=-1) * np.linalg.norm(lq, axis=-1))
    assert cos.min() > 0.995


def test_quantized_prefill_then_decode_matches_quantized_forward(qparams):
    """Exactness-between-forms survives quantization: the int8 GEMMs
    accumulate in exact int32, so the quantized family agrees across
    execution forms at float roundoff — same contract, same tolerance
    as the float tests."""
    rng = np.random.default_rng(2)
    toks = rng.integers(0, V, (2, 10)).astype(np.int32)
    oracle = np.asarray(causal_lm.lm_forward(qparams, jnp.asarray(toks), H))
    P = 4
    logits, k, v, pos = causal_lm.lm_prefill(
        qparams, jnp.asarray(toks[:, :P]), H, T)
    np.testing.assert_allclose(np.asarray(logits), oracle[:, P - 1],
                               rtol=2e-4, atol=2e-5)
    for t in range(P, 10):
        logits, k, v, pos = causal_lm.lm_decode_step(
            qparams, jnp.asarray(toks[:, t:t + 1]), k, v, pos, H)
        np.testing.assert_allclose(
            np.asarray(logits), oracle[:, t], rtol=2e-4, atol=2e-5,
            err_msg=f"quantized step {t} diverged")
    assert int(np.asarray(pos)[0]) == 10


def test_quantized_verify_window_matches_steps(qparams):
    """Speculative-decoding verify windows run the same quantized GEMMs:
    a W=3 window equals 3 single steps bit-for-bit in the int8 products
    (float roundoff overall)."""
    rng = np.random.default_rng(3)
    toks = rng.integers(0, V, (1, 9)).astype(np.int32)
    P = 3
    _, k1, v1, p1 = causal_lm.lm_prefill(
        qparams, jnp.asarray(toks[:, :P]), H, T)
    k2, v2, p2 = k1, v1, p1
    win, kw, vw, pw = causal_lm.lm_verify_window(
        qparams, jnp.asarray(toks[:, P:P + 3]), k1, v1, p1, H)
    for j in range(3):
        step, k2, v2, p2 = causal_lm.lm_decode_step(
            qparams, jnp.asarray(toks[:, P + j:P + j + 1]), k2, v2, p2, H)
        np.testing.assert_allclose(np.asarray(win[:, j]), np.asarray(step),
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kw), np.asarray(k2),
                               rtol=1e-5, atol=1e-6)


def test_filter_w8a8_option_serves_lm():
    """custom="quant=w8a8" on the tensor_filter surface: the zoo LM's
    decode step serves int8 end-to-end, logits close to the float
    filter's (and the metadata records the mode)."""
    from nnstreamer_tpu.models.causal_lm import empty_cache
    from nnstreamer_tpu.single import SingleShot

    spec = f"zoo://causal_lm?vocab={V}&dim=32&heads=4&layers=2&max_len=8"
    s_f = SingleShot(model=spec, framework="xla-tpu")
    s_q = SingleShot(model=spec, framework="xla-tpu", custom="quant=w8a8")
    assert s_q.fw._bundle.metadata["quantized"] == "w8a8"

    tok = np.asarray([[3]], np.int32)
    k, v, pos = empty_cache(2, 1, 4, 8, 8)
    lf = np.asarray(s_f.invoke(tok, k, v, pos)[0])
    lq = np.asarray(s_q.invoke(tok, k, v, pos)[0])
    assert lf.shape == lq.shape
    assert np.abs(lq - lf).max() < 0.06 * max(np.abs(lf).max(), 1e-6)


def test_w8a8_rejects_non_lm_bundle():
    from nnstreamer_tpu.models.quantize import quantize_bundle_w8a8
    from nnstreamer_tpu.models.zoo import get_model

    b = get_model("zoo://mobilenet_v2?width=0.25&size=32&num_classes=16"
                  "&dtype=float32")
    with pytest.raises(ValueError, match="w8a8"):
        quantize_bundle_w8a8(b)


@pytest.mark.parametrize("n_model", [2, 4])
def test_tp_decode_quantized_matches_single_device(qparams, n_model):
    """Distributed int8 decode: head-sharded TP generate over a w8a8
    tree equals the single-device quantized decode loop token-for-token.
    The design makes this EXACT, not approximate: column-sharded int8
    weights keep their single-device codes/grids, activations quantize
    on pmax-global grids, and row-sharded partials are summed in exact
    int32 before one global rescale (parallel/tp_decode.py
    _restructure_w8a8 + ops/int8.quant_act_global)."""
    from jax.sharding import Mesh

    from nnstreamer_tpu.parallel.tp_decode import (
        make_tp_generate, tp_shard_cache, tp_shard_params)

    if len(jax.devices()) < n_model:
        pytest.skip("needs virtual multi-device CPU")
    mesh = Mesh(np.array(jax.devices()[:n_model]), ("model",))
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, V, (2, 7)).astype(np.int32)
    n_steps = 8  # pos 7 + 8 steps = 15 <= max_len 16

    logits, kc, vc, pos = causal_lm.lm_prefill(
        qparams, jnp.asarray(prompt), H, T)
    first = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    want, tok = [], first
    kc1, vc1, p1 = kc, vc, pos
    for _ in range(n_steps):
        lg, kc1, vc1, p1 = causal_lm.lm_decode_step(
            qparams, tok, kc1, vc1, p1, H)
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        want.append(np.asarray(tok[:, 0]))
    want = np.stack(want, 1)

    tp = tp_shard_params(qparams, H, mesh)
    kc_tp, vc_tp = tp_shard_cache(kc, vc, L, 2, H, mesh)
    gen = make_tp_generate(H, T, mesh)
    got = np.asarray(gen(tp, first, kc_tp, vc_tp, pos, n_steps))
    np.testing.assert_array_equal(got, want)


def test_tp_shard_params_quantized_layout():
    """Sliced int8 payloads/scales must equal the single-device codes'
    slices (grid preservation is the whole design)."""
    from jax.sharding import Mesh

    from nnstreamer_tpu.ops.int8 import W8A8_TAG
    from nnstreamer_tpu.parallel.tp_decode import tp_shard_params

    if len(jax.devices()) < 2:
        pytest.skip("needs virtual multi-device CPU")
    p = causal_lm.init_causal_lm(jax.random.PRNGKey(5), V, D, H, 1, 8)
    qp = causal_lm.quantize_lm_params(p)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    tp = tp_shard_params(qp, H, mesh)

    qw = np.asarray(qp["wqkv"][W8A8_TAG])   # (1, D, 3D)
    wq0 = np.asarray(tp["wq"][W8A8_TAG])[0, 0]   # device 0: (D, hn*hd)
    np.testing.assert_array_equal(wq0, qw[0, :, :D // 2])
    np.testing.assert_array_equal(
        np.asarray(tp["wo_s"]), np.asarray(qp["wo"]["s"]))
    assert wq0.dtype == np.int8


def test_serving_engine_runs_quantized(qparams):
    """The continuous-batching engine consumes a quantized tree through
    the same slot primitives (stack_shape introspection instead of
    .shape) — greedy output must equal the engine-free quantized
    generation path."""
    from nnstreamer_tpu.serving.lm_engine import LMEngine

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, V, (n,)).astype(np.int32) for n in (3, 5)]
    gen = 4

    eng = LMEngine(qparams, H, T, n_slots=2, chunk=2)
    rids = [eng.submit(p, max_new=gen) for p in prompts]
    res = eng.run()

    for rid, p in zip(rids, prompts):
        logits, k, v, pos = causal_lm.lm_prefill(
            qparams, jnp.asarray(p[None]), H, T)
        want = [int(np.asarray(jnp.argmax(logits, -1))[0])]
        while len(want) < gen:
            logits, k, v, pos = causal_lm.lm_decode_step(
                qparams, jnp.asarray([[want[-1]]], dtype=jnp.int32),
                k, v, pos, H)
            want.append(int(np.asarray(jnp.argmax(logits, -1))[0]))
        assert res[rid] == want


@pytest.fixture(scope="module")
def lane_qparams():
    from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

    return causal_lm.quantize_lm_params(causal_lm.init_causal_lm(
        jax.random.PRNGKey(2), V, D, H, L, 2 * LANE_ROWS))


@pytest.mark.parametrize("windows,more", [(0, 7), (1, 0), (1, 14)])
def test_serving_engine_lane_runs_quantized(lane_qparams, windows, more):
    """The prompt lane goes through the same ``matmul_any`` / ``mlp_matmul``
    sites as the decode rows it rides with: an activation row has its own
    int8 grid, so a quantized tree's tokens through the lane are the
    whole-prompt path's, and the rows it leaves are the quantized
    ``lm_prefill``'s."""
    from nnstreamer_tpu.serving.lm_engine import LANE_ROWS, LMEngine

    max_len = 2 * LANE_ROWS
    t = windows * LANE_ROWS + more    # under, one, and over one window
    prompt = np.random.default_rng(t).integers(0, V, t).astype(np.int32)
    got = {}
    # a store that whole windows do not tile makes a whole-prompt engine
    for kind, cap in (("lane", max_len), ("whole", max_len - 8)):
        eng = LMEngine(lane_qparams, H, cap, n_slots=2, chunk=4)
        assert eng._lane == (kind == "lane")
        rid = eng.submit(prompt, max_new=6)
        got[kind] = eng.run()[rid]
        if kind == "lane":
            kc, vc = np.asarray(eng._kc)[0], np.asarray(eng._vc)[0]
    assert got["lane"] == got["whole"]
    _, k, v, _ = causal_lm.lm_prefill(
        lane_qparams, jnp.asarray(prompt[None]), H, max_len)
    np.testing.assert_allclose(kc[:, :t], np.asarray(k)[:, :t],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(vc[:, :t], np.asarray(v)[:, :t],
                               rtol=1e-5, atol=1e-5)
