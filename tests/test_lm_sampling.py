"""On-device sampling (serving/sampling.py + LMEngine sampled decode).

Contracts pinned here:
- defaults are greedy and bit-identical to isolated greedy generation;
- top_k=1 degenerates to greedy at any temperature;
- sampled streams are reproducible (seeded) and independent of batch
  composition / chunking (the fold_in(seed, consumed) key schedule);
- the sampler's keep-sets honor top-k and nucleus cuts, and its draw
  frequencies track the softmax distribution.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving import sampling

V, D, H, L, MAXLEN = 97, 32, 4, 2, 64


@pytest.fixture(scope="module")
def params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(7), V, D, H, L, MAXLEN)


def prompts_rng(n, lo=1, hi=24, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


def solo_run(params, prompt, max_new, **kw):
    """Isolated-run oracle: a 1-slot engine (chunk=1, exact bucketing is
    irrelevant to the contract — sampling keys depend only on consumed
    count and seed, which this also exercises)."""
    eng = LMEngine(params, H, MAXLEN, n_slots=1, chunk=1)
    rid = eng.submit(prompt, max_new, **kw)
    return eng.run()[rid]


# -- sampler unit behavior (synthetic logits) ----------------------------- #

def _draws(logits_row, n, temperature=1.0, top_k=0, top_p=1.0, seed=3):
    row = jnp.asarray(logits_row, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    toks = jax.vmap(lambda k: sampling.sample_row(
        row, k, jnp.float32(temperature), jnp.int32(top_k),
        jnp.float32(top_p)))(keys)
    return np.asarray(toks)


def test_topk_draws_stay_in_topk_set():
    logits = np.array([5.0, 4.0, 3.0, 2.0, 1.0, 0.0])
    toks = _draws(logits, 300, temperature=2.0, top_k=3)
    assert set(toks.tolist()) <= {0, 1, 2}
    assert len(set(toks.tolist())) > 1  # actually sampling, not argmax


def test_topp_keeps_minimal_prefix():
    # probs ~ [0.5, 0.3, 0.15, 0.05]; top_p=0.7 keeps {0, 1} only
    probs = np.array([0.5, 0.3, 0.15, 0.05])
    logits = np.log(probs)
    toks = _draws(logits, 300, temperature=1.0, top_p=0.7)
    assert set(toks.tolist()) <= {0, 1}
    assert len(set(toks.tolist())) == 2


def test_temperature_zero_is_argmax_and_frequencies_track_softmax():
    logits = np.array([1.0, 2.0, 0.5, 1.5])
    assert (_draws(logits, 50, temperature=0.0) == 1).all()
    toks = _draws(logits, 4000, temperature=1.0, seed=11)
    freq = np.bincount(toks, minlength=4) / 4000.0
    want = np.exp(logits) / np.exp(logits).sum()
    assert np.abs(freq - want).max() < 0.05


def test_disabled_filters_match_plain_softmax_sampling():
    # top_k=0 / top_p=1 must not perturb the categorical draw
    logits = np.array([0.3, -1.2, 2.0, 0.0, 1.1])
    a = _draws(logits, 64, temperature=1.3, top_k=0, top_p=1.0, seed=5)
    key = jax.random.PRNGKey(5)
    b = np.asarray(jax.vmap(lambda k: jax.random.categorical(
        k, jnp.asarray(logits / 1.3, jnp.float32)))(
            jax.random.split(key, 64)))
    assert (a == b).all()


def test_disabled_topp_keeps_saturated_tail_drawable():
    # peaked distribution over a big vocab: the float32 cumsum hits 1.0
    # after a couple of entries; disabled top_p must still keep the
    # sub-1e-7 tail bit-identical to a plain categorical draw
    logits = np.full(4096, -20.0)
    logits[:2] = [10.0, 0.0]
    a = _draws(logits, 256, temperature=1.0, top_k=0, top_p=1.0, seed=13)
    b = np.asarray(jax.vmap(lambda k: jax.random.categorical(
        k, jnp.asarray(logits, jnp.float32)))(
            jax.random.split(jax.random.PRNGKey(13), 256)))
    assert (a == b).all()


# -- engine-level contracts ---------------------------------------------- #

def test_default_submit_is_greedy_unchanged(params):
    prompt = prompts_rng(1, lo=5, hi=6)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rid = eng.submit(prompt, max_new=12)
    got = eng.run()[rid]
    # greedy oracle: unpadded prefill + step-at-a-time argmax
    logits, kc, vc, pos = causal_lm.lm_prefill(
        params, jnp.asarray(prompt[None]), H, MAXLEN)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < 12:
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, kc, vc, pos = causal_lm.lm_decode_step(
            params, tok, kc, vc, pos, H)
        out.append(int(jnp.argmax(logits[0])))
    assert got == out


def test_topk1_equals_greedy_any_temperature(params):
    prompt = prompts_rng(1, lo=8, hi=9, seed=2)[0]
    greedy = solo_run(params, prompt, 10)
    hot = solo_run(params, prompt, 10, temperature=5.0, top_k=1, seed=9)
    assert hot == greedy


def test_sampled_reproducible_and_seed_sensitive(params):
    prompt = prompts_rng(1, lo=6, hi=7, seed=3)[0]
    a = solo_run(params, prompt, 16, temperature=1.0, seed=41)
    b = solo_run(params, prompt, 16, temperature=1.0, seed=41)
    c = solo_run(params, prompt, 16, temperature=1.0, seed=42)
    assert a == b
    assert a != c  # 16 draws over V=97 colliding fully is ~impossible


def test_batched_sampling_matches_isolated(params):
    """The exactness contract extended to sampled decoding: output
    depends only on (request, seed), not slots/admission/chunking."""
    prompts = prompts_rng(6, seed=4)
    modes = [dict(temperature=1.0, seed=10),
             dict(),  # greedy in the same batch
             dict(temperature=0.7, top_k=8, seed=11),
             dict(temperature=1.3, top_p=0.9, seed=12),
             dict(temperature=0.9, top_k=20, top_p=0.8, seed=13),
             dict(temperature=2.0, seed=10)]
    eng = LMEngine(params, H, MAXLEN, n_slots=3, chunk=5)
    rids = [eng.submit(p, max_new=7 + i, **m)
            for i, (p, m) in enumerate(zip(prompts, modes))]
    res = eng.run()
    for i, (rid, p, m) in enumerate(zip(rids, prompts, modes)):
        assert res[rid] == solo_run(params, p, 7 + i, **m), f"req {i}"


def test_sampled_eos_stops_stream(params):
    prompt = prompts_rng(1, lo=6, hi=7, seed=8)[0]
    ref = solo_run(params, prompt, 24, temperature=1.1, seed=3)
    eos = ref[len(ref) // 2]  # a token the sampled stream will emit
    got = solo_run(params, prompt, 24, eos=eos, temperature=1.1, seed=3)
    assert got == ref[:ref.index(eos) + 1]


# -- the prompt lane: the first token is drawn inside the decode chunk ----- #

from nnstreamer_tpu.serving.lm_engine import LANE_ROWS  # noqa: E402

LANE_MAXLEN = 2 * LANE_ROWS
LANE_MODES = {
    "greedy": dict(),
    "temperature": dict(temperature=1.0, seed=10),
    "top_k": dict(temperature=0.7, top_k=8, seed=11),
    "top_p": dict(temperature=1.3, top_p=0.9, seed=2**31 + 12),
}


@pytest.fixture(scope="module")
def lane_params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(5), V, D, H, L, LANE_MAXLEN)


@pytest.mark.parametrize("seed", [0, 1, 41, 2**31 - 1, 2**31, 2**32 - 1,
                                  2**32 + 5, -1, -12345])
def test_seed_key_host_is_seed_key(seed):
    """The key an admission through the lane writes into the host's array
    is the key ``PRNGKey`` makes on the device."""
    np.testing.assert_array_equal(
        sampling.seed_key_host(seed), np.asarray(sampling.seed_key(seed)))


@pytest.mark.parametrize("mode", sorted(LANE_MODES))
@pytest.mark.parametrize("t", [9, LANE_ROWS, LANE_ROWS + 17])
def test_lane_sampled_tokens_match_whole_prompt_path(lane_params, t, mode):
    """A prompt prefilled through the lane draws the tokens the
    whole-prompt prefill program draws: the first from
    ``fold_in(seed key, prompt length)``, the rest by the same schedule."""
    prompt = np.random.default_rng(t).integers(0, V, t).astype(np.int32)
    got = {}
    # a store that whole windows do not tile makes a whole-prompt engine
    for kind, cap in (("lane", LANE_MAXLEN), ("whole", LANE_MAXLEN - 8)):
        eng = LMEngine(lane_params, H, cap, n_slots=2, chunk=4)
        assert eng._lane == (kind == "lane")
        rid = eng.submit(prompt, 8, **LANE_MODES[mode])
        got[kind] = eng.run()[rid]
    assert got["lane"] == got["whole"] and len(got["lane"]) == 8


def test_lane_sampled_streams_join_a_sampled_batch(lane_params):
    """Sampled and greedy prompts that follow each other through the lane
    while a sampled stream decodes: every stream's tokens are its lone
    run's through the whole-prompt path, and a finished sampled stream
    leaves its slot greedy."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, V, n).astype(np.int32)
               for n in (6, LANE_ROWS + 3, 20, LANE_ROWS)]
    modes = [LANE_MODES[m] for m in ("top_p", "temperature", "greedy",
                                     "top_k")]
    new = [20, 6, 9, 7]

    def lone(p, m, mode):
        eng = LMEngine(lane_params, H, LANE_MAXLEN - 8, n_slots=1, chunk=1)
        assert not eng._lane
        rid = eng.submit(p, m, **mode)
        return eng.run()[rid]

    eng = LMEngine(lane_params, H, LANE_MAXLEN, n_slots=3, chunk=4)
    rids = [eng.submit(prompts[0], new[0], **modes[0])]
    eng.step_iteration()
    eng.step_iteration()
    rids += [eng.submit(p, m, **mode)
             for p, m, mode in zip(prompts[1:], new[1:], modes[1:])]
    res = eng.run()
    for rid, p, m, mode in zip(rids, prompts, new, modes):
        assert res[rid] == lone(p, m, mode)
    assert not eng._temp.any() and not eng._topk.any() \
        and (eng._topp == 1.0).all()
