"""The second family ``LMEngine`` serves: GLM-4.7-Flash's block
(models/glm_moe_lite.py: latent attention over one stored row a token, a
dense first layer, sigmoid-routed experts with a shared one), its two
kernels (ops/pallas/latent_attention.py, ops/pallas/moe_gemm.py, through
the Pallas interpreter as tests/test_pallas.py runs ``decode_attention``)
and the engine around it.

The oracle is the benchmark's plain reference
(benchmark/reference/glm47_flash.py: naive attention, a loop over experts,
its own weights from the seed, nothing of the program). Logits are
compared, never sampled tokens. Tolerances: the program's absorbed
attention, its grouped GEMM and the reference's naive forms contract the
same float32 products in different orders, so logits of size ~0.3 at this
toy size agree to a few 1e-7; 5e-6 leaves room for a longer sequence and
would not pass one bfloat16 pass (3e-3) nor three (2e-5 on the latent
rows, ``benchmark/tests/test_correct_glm47.py``).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import glm47_flash as builder
from benchmark.reference.glm47_flash import Reference
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.models import glm_moe_lite as glm
from nnstreamer_tpu.ops.pallas import latent_attention as la
from nnstreamer_tpu.ops.pallas import moe_gemm as mg
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "configs",
                       "tiny-glm47-selftest.json")) as f:
    CFG = json.load(f)
SEED = 11
MAXLEN = 4 * LANE_ROWS
TOL = dict(rtol=0, atol=5e-6)


@pytest.fixture(scope="module")
def params():
    return builder.make_params(CFG, SEED)


@pytest.fixture(scope="module")
def block():
    return builder.block_of(CFG)


@pytest.fixture(scope="module")
def reference():
    return Reference(CFG, SEED)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


# --------------------------------------------------------------------------- #
# the step: lane windows, then decode rows, against the full forward pass
# --------------------------------------------------------------------------- #

def test_prefill_through_the_lane_then_decode_match_the_full_forward(
        params, block, reference):
    """A prompt of 2.3 windows goes through the lane of slot 1 while slot 0
    decodes another sequence; then slot 1 decodes. Every row of logits the
    steps return is the reference's full forward pass at that position."""
    s = 2
    a, b = prompt(150, 1), prompt(40, 2)
    kshape, vshape = glm.store_shapes(params, s, MAXLEN)
    cc, rc = jnp.zeros(kshape), jnp.zeros(vshape)
    step = jax.jit(glm.decode_step_slots, static_argnums=5)
    want_a, want_b = reference.logits(a), reference.logits(b)
    # slot 0 takes b's first rows one token a step (as decode rows) while
    # the lane carries a's windows into slot 1
    pos = jnp.zeros((s, 1), jnp.int32)
    active = jnp.array([True, False])
    at = 0
    for j in range(-(-a.size // LANE_ROWS)):
        count = min(LANE_ROWS, a.size - at)
        window = np.zeros(LANE_ROWS, np.int32)
        window[:count] = a[at:at + count]
        tokens = jnp.array([[[b[j]]], [[0]]], jnp.int32)
        logits, cc, rc, pos, counts = step(
            params, tokens, cc, rc, pos, block, active,
            (jnp.asarray(window), 1, at, count))
        np.testing.assert_allclose(logits[0, 0], want_b[j], **TOL)
        np.testing.assert_allclose(logits[2, 0], want_a[at + count - 1],
                                   **TOL)
        # both expert layers: the picks of 1 decode row + count lane rows
        assert int(counts[1]) == 2 * (1 + count) * block.top_k
        at += count
    # now slot 1 decodes a's next tokens, from the store the lane filled
    more = prompt(5, 3)
    seq = np.concatenate([a, more])
    want = reference.logits(seq)
    pos = pos.at[1, 0].set(a.size)
    for j, tok in enumerate(more):
        tokens = jnp.array([[[0]], [[tok]]], jnp.int32)
        logits, cc, rc, pos, _ = step(params, tokens, cc, rc, pos, block,
                                      jnp.array([False, True]), None)
        np.testing.assert_allclose(logits[1, 0], want[a.size + j], **TOL)


def test_a_row_past_capacity_is_poisoned(params, block):
    kshape, vshape = glm.store_shapes(params, 2, MAXLEN)
    logits, *_ = glm.decode_step_slots(
        params, jnp.zeros((2, 1, 1), jnp.int32), jnp.zeros(kshape),
        jnp.zeros(vshape), jnp.array([[3], [MAXLEN]], jnp.int32), block)
    assert np.isfinite(np.asarray(logits[0])).all()
    assert np.isnan(np.asarray(logits[1])).all()


# --------------------------------------------------------------------------- #
# attention: absorbed against naive, the kernel against the dense form
# --------------------------------------------------------------------------- #

def _attention_case(b=3, h=3, c=128, r=8, layers=2, m=512, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qa = jax.random.normal(ks[0], (b, h, c))
    qr = jax.random.normal(ks[1], (b, h, r))
    cn, rn = jax.random.normal(ks[2], (b, c)), jax.random.normal(ks[3], (b, r))
    cc = la.to_planes(jax.random.normal(ks[4], (b, layers, m, c)))
    rc = jnp.pad(jax.random.normal(ks[5], (b, layers, m, r)),
                 ((0, 0),) * 3 + ((0, la.LANES - r),))
    return qa, qr, cn, rn, cc, rc


def test_absorbed_attention_is_the_naive_one():
    """``q_n . (c W_k) + q_r . k_r`` and ``sum p (c W_v)`` computed head by
    head from expanded keys and values equal the absorbed form's ``(q_n
    W_k^T) . c`` and ``(sum p c) W_v``: the same sums, reassociated."""
    h, n, r, v, c, t = 3, 24, 8, 32, 128, 37
    blk = glm.GlmBlock(h, n, r, v, top_k=2, route_scale=1.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    kv_b = jax.random.normal(ks[0], (c, h * (n + v))) / np.sqrt(c)
    qn = jax.random.normal(ks[1], (1, h, n))
    qr = jax.random.normal(ks[2], (1, h, r))
    lat = jax.random.normal(ks[3], (t, c))
    kr = jax.random.normal(ks[4], (t, r))
    # naive: expand every stored token's keys and values
    kvb = np.asarray(lat @ kv_b, np.float64).reshape(t, h, n + v)
    kn, val = kvb[..., :n], kvb[..., n:]
    s = (np.einsum("hd,khd->hk", np.asarray(qn[0], np.float64), kn)
         + np.einsum("hr,kr->hk", np.asarray(qr[0], np.float64),
                     np.asarray(kr, np.float64))) * blk.sm_scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    naive = np.einsum("hk,khv->hv", p, val)
    # absorbed, through the dense form: rows 0 .. t-2 stored, t-1 new
    wk_b, wv_b = glm.split_kv_b(kv_b, blk)
    qa = jnp.einsum("rhn,hnc->rhc", qn, wk_b)
    m = 64
    cc = la.to_planes(jnp.zeros((1, 1, m, c)).at[0, 0, :t - 1].set(lat[:-1]))
    rc = jnp.zeros((1, 1, m, la.LANES)).at[0, 0, :t - 1, :r].set(kr[:-1])
    o, _, _ = la.latent_window_reference(
        qa, qr, lat[-1:], kr[-1:], cc, rc, 0, jnp.array([t - 1]),
        sm_scale=blk.sm_scale)
    got = jnp.einsum("rhc,hcv->rhv", o, wv_b)[0]
    np.testing.assert_allclose(got, naive, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("pos,active", [
    ([0, 1, 255], [True, True, True]),          # nothing stored; block edge
    ([256, 400, 7], [True, True, False]),       # two blocks; an empty slot
    ([511, 512, 300], [True, True, True]),      # the last row; past capacity
])
@pytest.mark.parametrize("li", [0, 1])
def test_latent_decode_kernel_is_the_dense_form(pos, active, li):
    """The kernel through the interpreter against the dense masked form:
    the same rows attended (float32 rounding of the online softmax apart:
    2e-6), the same new rows written and no others touched (exactly). A
    stream past capacity writes nothing in either; what it attends is not
    compared (the step poisons its logits)."""
    case = _attention_case()
    args = (*case, li, jnp.array(pos), jnp.array(active))
    want = la.latent_window_reference(*args, sm_scale=0.17)
    got = la.latent_decode_attention(*args, sm_scale=0.17, interpret=True)
    fits = np.array(pos) < case[-1].shape[2]
    np.testing.assert_allclose(got[0][fits], want[0][fits], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_lane_attention_is_the_step_by_step_one():
    """A window's row j, written and attended by ``latent_lane_attention``,
    gets what a one-token step at position pos0 + j gets from the same
    store."""
    p, pos0, slot, li = 64, 128, 1, 1
    qa, qr, cn, rn, cc, rc = _attention_case(b=p)
    cc, rc = cc[:3], rc[:3]
    o, cc2, rc2 = la.latent_lane_attention(
        qa, qr, cn, rn, cc, rc, li, slot, pos0, sm_scale=0.2)
    for j in (0, 17, 63):
        z = lambda x: jnp.zeros((3,) + x.shape[1:]).at[slot].set(x[j])  # noqa
        want = la.latent_window_reference(
            z(qa), z(qr), z(cn), z(rn), cc2, rc2, li,
            jnp.full((3,), pos0 + j), sm_scale=0.2)[0][slot]
        np.testing.assert_allclose(o[j], want, rtol=2e-6, atol=2e-6)


# --------------------------------------------------------------------------- #
# the router and the experts
# --------------------------------------------------------------------------- #

def test_the_router_picks_with_the_bias_and_weighs_without_it():
    blk = glm.GlmBlock(2, 4, 4, 4, top_k=2, route_scale=1.8)
    # one row whose scores are sigmoid([2, 1, 0, -1]); the bias lifts
    # expert 3 over expert 1 for the selection only
    m = jnp.array([[1.0, 0.0]])
    layer = {"router": jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0] * 4]),
             "route_bias": jnp.array([0.0, 0.0, 0.0, 0.9])}
    weight, member = glm.route(m, layer, blk)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))
    assert member.tolist() == [[True, False, False, True]]
    want = np.zeros(4)
    want[[0, 3]] = s[[0, 3]] / (s[0] + s[3]) * 1.8
    np.testing.assert_allclose(weight[0], want, rtol=1e-6)
    # without the bias the two largest scores are picked
    layer["route_bias"] = jnp.zeros(4)
    weight, member = glm.route(m, layer, blk)
    assert member.tolist() == [[True, True, False, False]]
    np.testing.assert_allclose(weight[0].sum(), 1.8, rtol=1e-6)
    # a row that holds nothing picks nothing
    _, member = glm.route(m, layer, blk, live=jnp.array([False]))
    assert not member.any()


def _experts(r=40, d=128, f=256, e=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (r, d)),
            jax.random.normal(ks[1], (e, d, f)) / np.sqrt(d),
            jax.random.normal(ks[2], (e, d, f)) / np.sqrt(d),
            jax.random.normal(ks[3], (e, f, d)) / np.sqrt(f), ks[4])


@pytest.mark.parametrize("rows", [5, 40, 96])
def test_grouped_gemm_is_the_loop_over_experts(rows):
    """The kernel through the interpreter against ``sum_e w_e E_e(x)``
    looped over all experts; rows that hold nothing get zero. One-hot
    gathers and scatters are exact, so what differs is the order of the
    hidden width's sum: 2e-6 on values of size ~3."""
    x, wg, wu, wd, key = _experts(r=rows)
    topw, topi = jax.lax.top_k(jax.random.uniform(key, (rows, 8)), 2)
    live = jnp.arange(rows) < rows - 2
    weight, member = mg.pick_weights(topi, topw, live, 8)
    want = mg.expert_mlp_reference(x, weight, wg, wu, wd)
    got = mg.expert_gemm(x, weight, member, wg, wu, wd, k=2, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    assert not np.asarray(got[rows - 2:]).any()


def test_grouped_gemm_is_dropless_under_total_imbalance():
    """Every one of 96 rows picks the same two experts: each expert's group
    is three tiles long, and every row still gets both of its experts in
    full (a capacity of rows x k / experts = 24 would have dropped 72 of
    each expert's 96)."""
    rows = 96
    x, wg, wu, wd, _ = _experts(r=rows)
    topi = jnp.tile(jnp.array([[1, 5]]), (rows, 1))
    topw = jnp.tile(jnp.array([[0.7, 1.1]]), (rows, 1))
    weight, member = mg.pick_weights(topi, topw, None, 8)
    expert, n_tiles, sel, _ = mg.route_tiles(
        weight, member, mg.max_tiles(rows, 2, 8))
    assert int(n_tiles[0]) == 2 * 3
    assert expert[:6].tolist() == [1, 1, 1, 5, 5, 5]
    assert float(sel.sum()) == 2 * rows          # every pick is in a tile
    want = 0.7 * _one_expert(x, wg[1], wu[1], wd[1]) \
        + 1.1 * _one_expert(x, wg[5], wu[5], wd[5])
    got = mg.expert_gemm(x, weight, member, wg, wu, wd, k=2, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def _one_expert(x, wg, wu, wd):
    hi = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    a = hi(x, wg)
    return hi(a * jax.nn.sigmoid(a) * hi(x, wu), wd)


def test_the_kernels_lower_for_tpu_at_the_published_widths():
    """Both kernels at GLM-4.7-Flash's sizes (32 slots x 5 layers x 2048
    positions; 64 experts of 2048 x 1536) lower for the TPU platform with
    their Mosaic calls in, the latent stores aliased in and out."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)       # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)         # noqa: E731
    s, h, c, r, layers, m = 32, 20, 512, 64, 5, 2048

    def attend(qa, qr, cn, rn, cc, rc, li, pos, active):
        return la.latent_decode_attention(qa, qr, cn, rn, cc, rc, li, pos,
                                          active, sm_scale=1 / 16)

    text = jax.export.export(jax.jit(attend), platforms=["tpu"])(
        f32(s, h, c), f32(s, h, r), f32(s, c), f32(s, r),
        f32(s, layers, c // 128, m, 128), f32(s, layers, m, 128), i32(),
        i32(s), jax.ShapeDtypeStruct((s,), jnp.bool_)).mlir_module()
    assert "tpu_custom_call" in text and "output_operand_alias" in text

    rows, d, f, e = 96, 2048, 1536, 64
    gemm = functools.partial(mg.expert_gemm, k=4)
    text = jax.export.export(jax.jit(gemm), platforms=["tpu"])(
        f32(rows, d), f32(rows, e),
        jax.ShapeDtypeStruct((rows, e), jnp.bool_), f32(e, d, f),
        f32(e, d, f), f32(e, f, d)).mlir_module()
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def greedy(reference, prompt_ids, n):
    """What the whole-prompt order gives: the full forward pass over the
    prompt and everything generated so far, its last row's best token."""
    seq = list(prompt_ids)
    for _ in range(n):
        seq.append(int(np.argmax(reference.logits(seq)[-1])))
    return seq[len(prompt_ids):]


def test_lane_and_run_ahead_give_the_whole_prompt_orders_tokens(
        params, block, reference):
    """Requests of several lengths arrive while others decode, through the
    lane and the run-ahead order: each gets the tokens a full forward pass
    over its own sequence gives, whoever decodes beside it; the same engine
    in the old order gives the same tokens (its routing counts differ: the
    two orders decode different chunk tails past a request's end)."""
    arrivals = [(0, prompt(70, 1), 6), (0, prompt(5, 2), 9),
                (1, prompt(130, 3), 5), (3, prompt(64, 4), 7)]

    def run(ahead):
        eng = LMEngine(params, block, MAXLEN, n_slots=2, chunk=4,
                       kv_page_size=0)
        assert eng._lane and eng._runs_ahead()
        if not ahead:
            eng._runs_ahead = lambda: False
        rids, it = [], 0
        while True:
            rids += [eng.submit(p, n) for at, p, n in arrivals if at == it]
            more = eng.step_iteration()
            it += 1
            if not more and it > 3:
                break
        return [eng.results[r] for r in rids], eng.stats

    outs, stats = run(ahead=True)
    for (_, p, n), out in zip(arrivals, outs):
        assert out == greedy(reference, p, n)
    old, old_stats = run(ahead=False)
    assert old == outs
    for key in ("lane_tokens", "tokens_out"):
        assert stats[key] == old_stats[key], key
    assert stats["lane_tokens"] == sum(p.size for _, p, _ in arrivals)
    assert stats["latent_rows_attended"] == stats["kv_rows_attended"] > 0
    # at most all 8 experts of both expert layers a step, at least top_k
    assert 2 * block.top_k * stats["decode_steps"] <= stats["experts_hit"] \
        <= 2 * 8 * stats["decode_steps"]
    assert stats["expert_rows"] % block.top_k == 0


def test_the_gpt2_tree_has_no_routing_counters():
    p = causal_lm.init_causal_lm(jax.random.PRNGKey(0), 97, 32, 4, 2, MAXLEN)
    eng = LMEngine(p, 4, MAXLEN, n_slots=2, kv_page_size=0)
    assert "experts_hit" not in eng.stats
    eng.submit(prompt(9, 0) % 97, 3)
    eng.run()
    assert eng._chunk_counts is None


@pytest.mark.parametrize("how,naming", [
    ("paged", "paged KV cache"),
    ("tp", "mesh-sharded engine"),
    ("spec", "speculative decoding"),
    ("quantized", "quantized tree"),
    ("quantize_call", "w8a8"),
    ("max_len", "prompt lane"),
])
def test_engines_that_cannot_serve_the_tree_say_so(params, block, how,
                                                   naming):
    """For the latent-attention tree only: the paged, mesh-sharded and
    speculative engines and a quantized tree are refused at construction,
    by name, and never miscompute."""
    with pytest.raises(ValueError, match=naming):
        if how == "paged":
            LMEngine(params, block, MAXLEN, kv_page_size=16)
        elif how == "tp":
            from jax.sharding import Mesh

            from nnstreamer_tpu.serving.tp_engine import TPLMEngine

            mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
            TPLMEngine(params, block, MAXLEN, mesh)
        elif how == "spec":
            LMEngine(params, block, MAXLEN, spec_draft=2, kv_page_size=0)
        elif how == "quantized":
            q = dict(params, head=params["head"].astype(jnp.bfloat16))
            LMEngine(q, block, MAXLEN, kv_page_size=0)
        elif how == "quantize_call":
            causal_lm.quantize_lm_params(params)
        else:
            LMEngine(params, block, MAXLEN + 8, kv_page_size=0)
