"""The third family ``LMEngine`` serves: Qwen3-Next's block
(models/qwen3_next.py: Gated DeltaNet layers with a recurrent state a slot,
grouped-query gated full attention, softmax-routed experts of which the
chip holds a share, a gated shared expert), its kernels
(ops/pallas/gated_delta.py, and ops/pallas/decode_attention.py with query
groups, through the Pallas interpreter) and the engine around it.

The oracle is the benchmark's plain reference
(benchmark/reference/qwen3_next.py: the delta rule token by token, naive
grouped attention, a loop over the held experts, its own weights from the
seed, nothing of the program). Logits are compared, never sampled tokens.
Tolerances: the program's chunked lane form, its kernels and the
reference's sequential and naive forms contract the same float32 products
in different orders; logits of size ~0.7 at this toy size agree to a few
1e-6 (the chunked form's triangular solve and the gates' exponentials add
most of it), and 2e-5 leaves room for a longer sequence and would not pass
three bfloat16 passes in every contraction (1.5e-3 on the logits, 2.5e-4
on the K/V rows, ``benchmark/tests/test_correct_qwen3next.py``).
The configuration is the benchmark's toy one with a full-attention layer
every second layer, so that two full layers (and two of the K/V stores'
layers) are exercised.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import qwen3_next as builder
from benchmark.reference import qwen3_next as ref_mod
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.models import qwen3_next as q3
from nnstreamer_tpu.ops.pallas import decode_attention as da
from nnstreamer_tpu.ops.pallas import gated_delta as gd
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "configs",
                       "tiny-qwen3next-selftest.json")) as f:
    CFG = {**json.load(f), "full_attention_interval": 2}
SEED = 13
MAXLEN = 4 * LANE_ROWS
TOL = dict(rtol=0, atol=2e-5)


@pytest.fixture(scope="module")
def params():
    return builder.make_params(CFG, SEED)


@pytest.fixture(scope="module")
def block():
    return builder.block_of(CFG)


@pytest.fixture(scope="module")
def reference():
    return ref_mod.Reference(CFG, SEED)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def stores(params, block, slots):
    def zeros(shape):
        if isinstance(shape[0], tuple):
            return tuple(zeros(s) for s in shape)
        return jnp.zeros(shape, jnp.float32)

    return zeros(q3.store_shapes(params, slots, MAXLEN, block))


def lane_steps(step, params, block, kv, st, pos, slot, seq, decode_tok):
    """``seq`` through the lane of ``slot``, a window a step, while the
    other slots decode ``decode_tok`` (a token a step, or None); returns
    the logits of each step and the stores."""
    out, at = [], 0
    s = pos.shape[0]
    active = jnp.arange(s) != slot if decode_tok is not None \
        else jnp.zeros((s,), bool)
    for j in range(-(-seq.size // LANE_ROWS)):
        count = min(LANE_ROWS, seq.size - at)
        window = np.zeros(LANE_ROWS, np.int32)
        window[:count] = seq[at:at + count]
        tok = 0 if decode_tok is None else decode_tok[j]
        tokens = jnp.full((s, 1, 1), tok, jnp.int32)
        logits, kv, st, pos, counts = step(
            params, tokens, kv, st, pos, block, active,
            (jnp.asarray(window), slot, at, count))
        out.append((logits, at, count, counts))
        at += count
    return out, kv, st, pos


# --------------------------------------------------------------------------- #
# the step: lane windows, then decode rows, against the full forward pass
# --------------------------------------------------------------------------- #

def test_prefill_through_the_lane_then_decode_match_the_full_forward(
        params, block, reference):
    """A prompt of 2.3 windows goes through the lane of slot 1 while slot 0
    decodes another sequence; then slot 1 decodes from the stores the lane
    filled. Every row of logits is the reference's full forward pass at its
    position, and the step's counts are the routing's and the states'."""
    a, b = prompt(150, 1), prompt(40, 2)
    kv, st = stores(params, block, 2)
    step = jax.jit(q3.decode_step_slots, static_argnums=5)
    want_a, want_b = reference.logits(a), reference.logits(b)
    pos = jnp.zeros((2, 1), jnp.int32)
    steps, kv, st, pos = lane_steps(step, params, block, kv, st, pos, 1, a, b)
    for j, (logits, at, count, counts) in enumerate(steps):
        np.testing.assert_allclose(logits[0, 0], want_b[j], **TOL)
        np.testing.assert_allclose(logits[2, 0], want_a[at + count - 1],
                                   **TOL)
        held, picks = int(counts[1]), int(counts[2])
        # 4 layers, 1 decode row + count lane rows, top_k picks each
        assert picks == 4 * (1 + count) * block.top_k
        assert 0 < held <= picks and 0 < int(counts[0]) <= 4 * 4
        assert int(counts[3]) == (1 + 1) * 2      # 2 slot-layers x 2 layers
    more = prompt(5, 3)
    want = reference.logits(np.concatenate([a, more]))
    pos = pos.at[1, 0].set(a.size)
    for j, tok in enumerate(more):
        tokens = jnp.array([[[0]], [[tok]]], jnp.int32)
        logits, kv, st, pos, counts = step(params, tokens, kv, st, pos,
                                           block, jnp.array([False, True]),
                                           None)
        np.testing.assert_allclose(logits[1, 0], want[a.size + j], **TOL)
        assert int(counts[3]) == 2


def test_a_refilled_slot_gives_a_fresh_engines_logits(params, block):
    """Slot 0 prefills and decodes one request, then the lane gives it a
    second: the second's logits are those of stores never written, so
    nothing of the first survives in the state, the convolution or the
    K/V rows a window reads."""
    step = jax.jit(q3.decode_step_slots, static_argnums=5)
    first, second = prompt(100, 4), prompt(70, 5)
    kv, st = stores(params, block, 1)
    pos = jnp.zeros((1, 1), jnp.int32)
    _, kv, st, pos = lane_steps(step, params, block, kv, st, pos, 0, first,
                                None)
    pos = pos.at[0, 0].set(first.size)
    for tok in prompt(9, 6):
        _, kv, st, pos, _ = step(params, jnp.full((1, 1, 1), tok), kv, st,
                                 pos, block, jnp.array([True]), None)
    used, *_ = lane_steps(step, params, block, kv, st, pos, 0, second,
                          None)
    fresh, *_ = lane_steps(step, params, block, *stores(params, block, 1),
                             jnp.zeros((1, 1), jnp.int32), 0, second, None)
    for (a, *_), (b, *_) in zip(used, fresh):
        np.testing.assert_array_equal(np.asarray(a[-1]), np.asarray(b[-1]))


def test_an_empty_slot_keeps_its_state_across_a_chunk(params, block):
    """A chunk of decode steps in which slot 1 holds no request leaves its
    recurrent state, its convolution rows and its K/V rows as they were,
    to the bit, while slot 0's move."""
    kv, st = stores(params, block, 2)
    rng = np.random.default_rng(0)
    kv = tuple(jnp.asarray(rng.normal(size=z.shape), jnp.float32)
               for z in kv)
    st = (jnp.asarray(rng.normal(size=st[0].shape) * 0.1, jnp.float32),
          tuple(jnp.asarray(rng.normal(size=z.shape), jnp.float32)
                for z in st[1]))
    before = jax.tree_util.tree_map(np.asarray, (kv, st))
    step = jax.jit(q3.decode_step_slots, static_argnums=5)
    pos = jnp.array([[40], [57]], jnp.int32)
    active = jnp.array([True, False])
    for tok in prompt(4, 7):
        _, kv, st, pos, counts = step(params, jnp.full((2, 1, 1), tok), kv,
                                      st, pos, block, active, None)
        assert int(counts[3]) == 2
    after = jax.tree_util.tree_map(np.asarray, (kv, st))
    for old, new in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(old[1], new[1])
        assert not np.array_equal(old[0], new[0])


def test_a_row_past_capacity_is_poisoned(params, block):
    kv, st = stores(params, block, 2)
    logits, *_ = q3.decode_step_slots(
        params, jnp.zeros((2, 1, 1), jnp.int32), kv, st,
        jnp.array([[3], [MAXLEN]], jnp.int32), block)
    assert np.isfinite(np.asarray(logits[0])).all()
    assert np.isnan(np.asarray(logits[1])).all()


# --------------------------------------------------------------------------- #
# the delta rule: kernel against dense, chunked against sequential
# --------------------------------------------------------------------------- #

def _delta_case(b, hk, h, dk, dv, layers=3, seed=0, rows=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = (b,) if rows is None else (rows,)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = l2(jax.random.normal(ks[0], n + (hk, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], n + (hk, dk)))
    v = jax.random.normal(ks[2], n + (h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], n + (h,)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], n + (h,)))
    state = jax.random.normal(ks[5], (b, layers, h, dk, dv)) * 0.1
    return q, k, v, g, beta, state


@pytest.mark.parametrize("active,li", [
    ((True, False, True, True), 1),
    ((True, True, True, True), 0),
    ((False, False, False, False), 2),
    ((False, True, False, False), 2),
])
def test_gated_delta_kernel_is_the_dense_form(active, li):
    """Through the Pallas interpreter: the kernel updates the active
    streams' states in place as the dense form does, leaves the others'
    (and every other layer's) to the bit, and gives the others zeros."""
    q, k, v, g, beta, state = _delta_case(4, 2, 4, 128, 128)
    act = jnp.array(active)
    want = gd.step_reference(q, k, v, jnp.exp(g), beta, state, li, act)
    got = gd.gated_delta_step(q, k, v, jnp.exp(g), beta, state, li, act,
                              interpret=True)
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-6)
    idle = ~np.asarray(act)
    np.testing.assert_array_equal(np.asarray(got[1])[idle],
                                  np.asarray(state)[idle])
    others = [i for i in range(state.shape[1]) if i != li]
    np.testing.assert_array_equal(np.asarray(got[1])[:, others],
                                  np.asarray(state)[:, others])


@pytest.mark.parametrize("rows", [1, 16, 64])
def test_chunked_window_is_the_sequential_rule(rows):
    q, k, v, g, beta, state = _delta_case(1, 2, 4, 16, 24, rows=rows)
    s0 = state[0, 0]
    want_o, want_s = gd.window_sequential(q, k, v, g, beta, s0)
    got_o, got_s = gd.window_chunked(q, k, v, g, beta, s0)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=2e-6)


@pytest.mark.parametrize("pos0,count", [(0, 64), (0, 23), (64, 41)])
def test_lane_window_runs_the_real_rows_from_the_slots_state(pos0, count):
    """The lane's window over its real rows only, from the slot's state or
    from zero where it starts a prompt; the state after its last real row
    is the one written back, and the other slots' are untouched."""
    q, k, v, g, beta, state = _delta_case(3, 2, 4, 16, 24, rows=64)
    o, new = gd.lane_window(q, k, v, g, beta, state, 1, 2, pos0, count)
    s0 = state[2, 1] if pos0 else jnp.zeros_like(state[2, 1])
    want_o, want_s = gd.window_sequential(
        q[:count], k[:count], v[:count], g[:count], beta[:count], s0)
    np.testing.assert_allclose(o[:count], want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(new[2, 1], want_s, rtol=0, atol=2e-6)
    keep = np.ones(state.shape[:2], bool)
    keep[2, 1] = False
    np.testing.assert_array_equal(np.asarray(new)[keep],
                                  np.asarray(state)[keep])


# --------------------------------------------------------------------------- #
# grouped attention: the kernel and the lane against equal heads
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("group,hd,pos", [
    (4, 128, (37, 0, 200)),
    (8, 256, (255, 3, 0)),
    (2, 256, (8, 31, 129)),
])
def test_grouped_decode_attention_is_the_equal_heads_form(group, hd, pos):
    """K/V head i serves query heads i*group .. i*group + group - 1: the
    grouped kernel (interpreter) and the grouped dense form give what
    equal heads give with each K/V head repeated, and write the new rows
    of the active streams (wider rows through their tile of 8)."""
    b, layers, hkv, m = 3, 2, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(group), 5)
    q = jax.random.normal(ks[0], (b, hkv * group, 1, hd))
    kn, vn = (jax.random.normal(kk, (b, hkv, 1, hd)) for kk in ks[1:3])
    kc, vc = (jax.random.normal(kk, (b, layers, hkv, m, hd))
              for kk in ks[3:5])
    pos, active = jnp.array(pos), jnp.array([True, False, True])
    rep = lambda z, ax: jnp.repeat(z, group, axis=ax)  # noqa: E731
    want = da.window_attention_reference(
        q, rep(kn, 1), rep(vn, 1), rep(kc, 2), rep(vc, 2), 1, pos, active,
        layer_axis=1)
    dense = da.window_attention_reference(q, kn, vn, kc, vc, 1, pos, active,
                                          layer_axis=1)
    kernel = da.decode_attention(q, kn, vn, kc, vc, 1, pos, active,
                                 layer_axis=1, interpret=True)
    for got in (dense, kernel):
        np.testing.assert_allclose(got[0], want[0], rtol=0, atol=2e-6)
        np.testing.assert_allclose(got[1], want[1][:, :, ::group],
                                   rtol=0, atol=0)
        np.testing.assert_allclose(got[2], want[2][:, :, ::group],
                                   rtol=0, atol=0)


# one stream at each edge of a block (256 rows at 512 positions) and of its
# tail's sub-tiles (32 rows): none, one, a sub-tile less one, a sub-tile, a
# block less one, a block, a block and a sub-tile and one
EDGES = (0, 1, 31, 32, 255, 256, 289)


@pytest.mark.parametrize("group,hd", [(8, 256), (4, 128), (2, 128)])
@pytest.mark.parametrize("active", ["all", "mixed"])
def test_grouped_decode_attention_at_block_and_tail_edges(group, hd, active):
    """The grouped kernel (interpreter) folds a K/V head's block into its
    group as two products, the group padded to 8 rows where it is smaller:
    at every edge of a block and of a tail's sub-tiles it gives the dense
    grouped form's output, and both stores to the bit; a stream that is not
    active writes nothing and gets its K/V head's value row back."""
    b, layers, hkv, m = len(EDGES), 2, 2, 512
    ks = jax.random.split(jax.random.PRNGKey(hd + group), 5)
    q = jax.random.normal(ks[0], (b, hkv * group, 1, hd))
    kn, vn = (jax.random.normal(kk, (b, hkv, 1, hd)) for kk in ks[1:3])
    kc, vc = (jax.random.normal(kk, (b, layers, hkv, m, hd))
              for kk in ks[3:5])
    pos = jnp.array(EDGES)
    act = jnp.arange(b) % 2 == 0 if active == "mixed" else \
        jnp.ones((b,), bool)
    want = da.window_attention_reference(q, kn, vn, kc, vc, 1, pos, act,
                                         layer_axis=1)
    got = da.decode_attention(q, kn, vn, kc, vc, 1, pos, act, layer_axis=1,
                              interpret=True)
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=2e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_grouped_lane_attention_is_the_equal_heads_form():
    hkv, group, hd, p = 2, 8, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (p, hkv * group, 1, hd))
    kn, vn = (jax.random.normal(kk, (p, hkv, 1, hd)) for kk in ks[1:3])
    kc, vc = (jax.random.normal(kk, (2, 2, hkv, 512, hd)) for kk in ks[3:5])
    rep = lambda z, ax: jnp.repeat(z, group, axis=ax)  # noqa: E731
    for pos0 in (0, 48, 496):
        want = da.lane_window_attention(q, rep(kn, 1), rep(vn, 1),
                                        rep(kc, 2), rep(vc, 2), 1, 1, pos0)
        got = da.lane_window_attention(q, kn, vn, kc, vc, 1, 1, pos0)
        np.testing.assert_allclose(got[0], want[0], rtol=0, atol=2e-6)
        np.testing.assert_array_equal(got[1], want[1][:, :, ::group])


# --------------------------------------------------------------------------- #
# the experts: a chip's share, and the shares against the whole layer
# --------------------------------------------------------------------------- #

def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(block):
    """Four chips share each layer, 4 of the router's 16 experts each: the
    program's expert layer on each share (its routed part; the shared
    expert, which every chip computes alike, counted once) adds up to the
    reference's layer with all 16 experts held."""
    n, held = CFG["deployment"]["router_experts"], CFG["num_experts"]
    x = jax.random.normal(jax.random.PRNGKey(9), (24, CFG["hidden_size"]))
    # the layer's input after its norm (weights zero: scale one), which the
    # reference's layer applies itself
    m = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + CFG["rms_norm_eps"])
    total, shared = 0.0, None
    for first in range(0, n, held):
        p = builder.make_params(CFG, SEED, experts=(first, held))
        layer = p["layers"][0]
        blk = builder.block_of({**CFG, "deployment": {
            **CFG["deployment"], "experts_held": [first, first + held]}})
        with jax.default_matmul_precision("float32"):
            y, counts = q3._moe(m, layer, blk, None)
        part = dict(layer, w_gate=layer["w_gate"][:0], w_up=layer["w_up"][:0],
                    w_down=layer["w_down"][:0])
        with jax.default_matmul_precision("float32"):
            s, _ = q3._moe(m, part, blk, None)
        total = total + (y - s)
        shared = s
        assert int(counts[2]) == x.shape[0] * block.top_k
    whole_cfg = {**CFG, "num_experts": n,
                 "deployment": {**CFG["deployment"], "experts_held": [0, n]}}
    ref = ref_mod.Reference(whole_cfg, SEED)
    m = ref.m
    weight, _ = ref_mod._route(x, ref.layers[0]["router"],
                               jnp.zeros((x.shape[0],), bool), m, False)
    want = ref_mod._experts(x, weight, {**ref.layers[0], **ref.experts(0)},
                            m, False) - x
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=2e-6)


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def greedy(reference, prompt_ids, n):
    seq = list(prompt_ids)
    for _ in range(n):
        seq.append(int(np.argmax(reference.logits(seq)[-1])))
    return seq[len(prompt_ids):]


def test_lane_and_run_ahead_give_the_whole_prompt_orders_tokens(
        params, block, reference):
    """Requests arrive while others decode and refill slots, through the
    lane and the run-ahead order: each gets the tokens a full forward pass
    over its own sequence gives; the counters count the routing and the
    states the steps updated."""
    arrivals = [(0, prompt(70, 1), 6), (0, prompt(5, 2), 9),
                (1, prompt(130, 3), 5), (3, prompt(64, 4), 7)]
    eng = LMEngine(params, block, MAXLEN, n_slots=2, chunk=4,
                   kv_page_size=0)
    assert eng._lane and eng._runs_ahead()
    rids, it = [], 0
    while True:
        rids += [eng.submit(p, n) for at, p, n in arrivals if at == it]
        more = eng.step_iteration()
        it += 1
        if not more and it > 3:
            break
    for (_, p, n), rid in zip(arrivals, rids):
        assert eng.results[rid] == greedy(reference, p, n)
    st = eng.stats
    assert st["lane_tokens"] == sum(p.size for _, p, _ in arrivals)
    assert st["held_picks"] <= st["picks"]
    assert st["picks"] % block.top_k == 0
    assert 0 < st["experts_hit"] <= 4 * CFG["num_experts"] \
        * st["decode_steps"]
    # 2 linear layers: a decoding slot or the lane's slot, a step each
    assert 0 < st["state_rows"] <= 2 * 3 * st["decode_steps"]
    assert st["state_rows"] % 2 == 0
    assert "latent_rows_attended" not in st


@pytest.mark.parametrize("how,naming", [
    ("paged", "paged KV cache"),
    ("tp", "mesh-sharded engine"),
    ("spec", "speculative decoding"),
    ("quantized", "quantized tree"),
    ("max_len", "prompt lane"),
])
def test_engines_that_cannot_serve_the_tree_say_so(params, block, how,
                                                   naming):
    with pytest.raises(ValueError, match=naming) as err:
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
        else:
            LMEngine(params, block, MAXLEN + 8, kv_page_size=0)
    if how != "tp":
        assert "models/qwen3_next.py" in str(err.value)


def test_each_static_description_names_its_family():
    assert causal_lm.family(4) is None
    assert causal_lm.family(builder.block_of(CFG)) is q3
    from benchmark.builders import glm47_flash

    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "tiny-glm47-selftest.json")) as f:
        glm = glm47_flash.block_of(json.load(f))
    assert causal_lm.family(glm).COUNTS == ("experts_hit", "expert_rows")
