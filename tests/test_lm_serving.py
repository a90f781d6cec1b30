"""Continuous-batching LM engine: greedy-exactness vs isolated decode.

Contract (serving/lm_engine.py): every stream's output matches isolated
single-stream generation token-for-token, regardless of batch
composition, admission time, chunk size, or prompt-length bucketing.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.serving import LMEngine, next_pow2_bucket
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

V, D, H, L, MAXLEN = 97, 32, 4, 2, 64


@pytest.fixture(scope="module")
def params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(7), V, D, H, L, MAXLEN)


def isolated_generate(params, prompt, max_new, eos=None):
    """Single-stream oracle: unpadded prefill + one-at-a-time decode."""
    logits, kc, vc, pos = causal_lm.lm_prefill(
        params, jnp.asarray(np.asarray(prompt, np.int32)[None]),
        H, MAXLEN)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < max_new and not (eos is not None and out[-1] == eos):
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, kc, vc, pos = causal_lm.lm_decode_step(
            params, tok, kc, vc, pos, H)
        out.append(int(jnp.argmax(logits[0])))
    return out


def prompts_rng(n, lo=1, hi=24, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


def test_single_request_matches_isolated(params):
    prompt = prompts_rng(1, lo=5, hi=6)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rid = eng.submit(prompt, max_new=12)
    got = eng.run()[rid]
    assert got == isolated_generate(params, prompt, 12)


def test_more_requests_than_slots_slot_reuse(params):
    prompts = prompts_rng(7, seed=1)
    eng = LMEngine(params, H, MAXLEN, n_slots=3, chunk=4)
    rids = [eng.submit(p, max_new=6 + i % 5) for i, p in enumerate(prompts)]
    res = eng.run()
    assert eng.stats["prefills"] == 7
    for i, (rid, p) in enumerate(zip(rids, prompts)):
        assert res[rid] == isolated_generate(params, p, 6 + i % 5), \
            f"request {i} diverged"


def test_mid_flight_admission(params):
    prompts = prompts_rng(5, seed=2)
    eng = LMEngine(params, H, MAXLEN, n_slots=4, chunk=2)
    rids = [eng.submit(p, max_new=10) for p in prompts[:2]]
    eng.step_iteration()
    eng.step_iteration()
    rids += [eng.submit(p, max_new=10) for p in prompts[2:]]
    res = eng.run()
    for rid, p in zip(rids, prompts):
        assert res[rid] == isolated_generate(params, p, 10)


@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_chunk_size_invariance(params, chunk):
    prompts = prompts_rng(4, seed=3)
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=chunk)
    rids = [eng.submit(p, max_new=9) for p in prompts]
    res = eng.run()
    for rid, p in zip(rids, prompts):
        assert res[rid] == isolated_generate(params, p, 9)


def test_eos_early_stop(params):
    # pick an eos the model actually emits: generate once, then use a
    # token from the middle of that stream as the eos marker
    prompt = prompts_rng(1, lo=8, hi=9, seed=4)[0]
    ref_free = isolated_generate(params, prompt, 20)
    eos = ref_free[len(ref_free) // 2]
    ref = isolated_generate(params, prompt, 20, eos=eos)
    assert ref[-1] == eos and len(ref) < 20
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rid = eng.submit(prompt, max_new=20, eos=eos)
    filler = prompts_rng(1, seed=5)[0]
    rid2 = eng.submit(filler, max_new=20)
    res = eng.run()
    assert res[rid] == ref
    assert res[rid2] == isolated_generate(params, filler, 20)
    # capacity invariant even with a mid-chunk eos: every slot-step
    # either produced a kept token or is counted as waste
    st = eng.stats
    assert eng.n_slots * st["decode_steps"] == \
        (st["tokens_out"] - st["prefills"]) + st["wasted_slot_steps"]


def test_max_new_one_retires_at_admission(params):
    prompt = prompts_rng(1, seed=6)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=1, chunk=4)
    rid = eng.submit(prompt, max_new=1)
    res = eng.run()
    assert res[rid] == isolated_generate(params, prompt, 1)
    # the one step is the lane's: it carried the prompt and gave the token
    assert eng.stats["decode_steps"] == eng.stats["lane_steps"] == 1
    assert eng.stats["slot_steps"] == 0


def test_capacity_boundary(params):
    # prompt + max_new - 1 == max_len exactly fills the cache
    t = MAXLEN - 8
    prompt = prompts_rng(1, lo=t, hi=t + 1, seed=7)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=1, chunk=16)
    rid = eng.submit(prompt, max_new=9)
    got = eng.run()[rid]
    ref = isolated_generate(params, prompt, 9)
    assert got == ref and not any(np.isnan(got))


def test_submit_rejections(params):
    eng = LMEngine(params, H, MAXLEN, n_slots=1)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(np.zeros(MAXLEN, np.int32), max_new=2)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new=2)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=0)


def test_bucketing_is_exact_and_bounded(params):
    # distinct prompt lengths land in few buckets: prefill compiles are
    # bounded by the bucket count, and results stay exact
    assert next_pow2_bucket(1) == 16 and next_pow2_bucket(17) == 32
    prompts = [np.arange(1, n + 1, dtype=np.int32) % V
               for n in (1, 3, 15, 16, 17, 31, 33)]
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rids = [eng.submit(p, max_new=5) for p in prompts]
    res = eng.run()
    for rid, p in zip(rids, prompts):
        assert res[rid] == isolated_generate(params, p, 5)


def test_masked_prefill_matches_unpadded(params):
    prompt = prompts_rng(1, lo=11, hi=12, seed=8)[0]
    t = prompt.size
    lg_ref, kc_ref, vc_ref, pos_ref = causal_lm.lm_prefill(
        params, jnp.asarray(prompt[None]), H, MAXLEN)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :t] = prompt
    lg, kc, vc, pos = causal_lm.lm_prefill_masked(
        params, jnp.asarray(padded), jnp.int32(t), H, MAXLEN)
    assert int(pos[0]) == int(pos_ref[0]) == t
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               rtol=1e-5, atol=1e-6)
    # cache rows BELOW true_len must match; rows past it are garbage by
    # contract (overwritten before visible)
    np.testing.assert_allclose(np.asarray(kc[:, :t]),
                               np.asarray(kc_ref[:, :t]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vc[:, :t]),
                               np.asarray(vc_ref[:, :t]),
                               rtol=1e-5, atol=1e-6)


def test_slot_step_matches_single_stream(params):
    # lm_decode_step_slots == stacked single-stream lm_decode_step
    rng = np.random.default_rng(9)
    S = 3
    states = []
    for s in range(S):
        prompt = rng.integers(0, V, 4 + 3 * s).astype(np.int32)
        lg, kc, vc, pos = causal_lm.lm_prefill(
            params, jnp.asarray(prompt[None]), H, MAXLEN)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)[:, None]
        states.append((tok, kc, vc, pos))
    toks = jnp.stack([s[0] for s in states])
    kcs = jnp.stack([s[1] for s in states])
    vcs = jnp.stack([s[2] for s in states])
    poss = jnp.stack([s[3] for s in states])
    lg_b, kcs2, vcs2, poss2 = causal_lm.lm_decode_step_slots(
        params, toks, kcs, vcs, poss, H)
    for s, (tok, kc, vc, pos) in enumerate(states):
        lg1, kc1, vc1, pos1 = causal_lm.lm_decode_step(
            params, tok, kc, vc, pos, H)
        np.testing.assert_allclose(np.asarray(lg_b[s]), np.asarray(lg1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(kcs2[s]), np.asarray(kc1),
                                   rtol=1e-5, atol=1e-6)
        assert int(poss2[s, 0]) == int(pos1[0])


def test_engine_exact_under_env_flash_flag(params, monkeypatch):
    # NNS_LM_FLASH=1 must not reroute the masked prefill onto the flash
    # path (which cannot column-mask a padded prompt): admission forces
    # dense and results stay exact
    monkeypatch.setenv("NNS_LM_FLASH", "1")
    prompt = prompts_rng(1, lo=6, hi=7, seed=12)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rid = eng.submit(prompt, max_new=8)
    got = eng.run()[rid]
    monkeypatch.delenv("NNS_LM_FLASH")
    assert got == isolated_generate(params, prompt, 8)


def test_nonpow2_chunk_kept_at_steady_state(params):
    # chunk=6 is not a power of two: full-size chunks must run 6 steps
    # (only TAIL chunks floor to pow2 for executable-cache bounding)
    prompt = prompts_rng(1, lo=4, hi=5, seed=13)[0]
    eng = LMEngine(params, H, MAXLEN, n_slots=1, chunk=6)
    rid = eng.submit(prompt, max_new=14)  # 1 prefill + 13 decode
    got = eng.run()[rid]
    assert got == isolated_generate(params, prompt, 14)
    # the prompt's one lane step, then of 13 remaining two chunks of 6
    # and the tail 1 (pow2), each read an iteration after its dispatch
    assert eng.stats["decode_steps"] == 1 + 13
    assert [r["chunk"] for r in eng.recent_steps()] == [1, 6, 6, 1]
    assert eng._seen_programs == {("lane", 6), ("chunk", 6), ("chunk", 1)}


def test_host_pos_mirror_tracks_device(params):
    prompts = prompts_rng(3, seed=14)
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    for p in prompts:
        eng.submit(p, max_new=7)
    eng.run()
    np.testing.assert_array_equal(
        np.asarray(eng._pos)[:, 0], np.asarray(eng._pos_host))


def test_paged_kv_same_tokens_as_contiguous(params):
    # the paged cache's engine-level exactness suite is
    # tests/test_kv_paging.py; this pins the serving contract from THIS
    # file's angle — kv_page_size is a scheduling knob, not a numerics
    # knob: same workload, same tokens, bit for bit
    prompts = prompts_rng(4, seed=15)
    cont = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    paged = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4, kv_page_size=8)
    rc = [cont.submit(p, max_new=8) for p in prompts]
    rp = [paged.submit(p, max_new=8) for p in prompts]
    res_c, res_p = cont.run(), paged.run()
    for rid_c, rid_p, p in zip(rc, rp, prompts):
        assert res_p[rid_p] == res_c[rid_c] == isolated_generate(params, p, 8)
    assert paged.kv_stats is not None and cont.kv_stats is None


def test_stats_account_for_waste(params):
    prompts = prompts_rng(2, seed=10)
    eng = LMEngine(params, H, MAXLEN, n_slots=4, chunk=4)
    rids = [eng.submit(p, max_new=3 + 5 * i) for i, p in enumerate(prompts)]
    res = eng.run()
    for rid, p, n in zip(rids, prompts, (3, 8)):
        assert res[rid] == isolated_generate(params, p, n)
    st = eng.stats
    assert st["prefills"] == 2
    assert st["tokens_out"] == 3 + 8
    # 2 empty slots ride every chunk; the short request wastes steps too
    assert st["wasted_slot_steps"] > 0
    assert st["slot_steps"] >= st["tokens_out"] - st["prefills"]
    assert eng.n_slots * st["decode_steps"] == \
        (st["tokens_out"] - st["prefills"]) + st["wasted_slot_steps"]


def forward_generate(params, prompt, max_new):
    """The oracle of oracles: greedy tokens from the full causal forward,
    one ``lm_forward`` a token, no cache at all."""
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(max_new):
        logits = causal_lm.lm_forward(
            params, jnp.asarray(np.asarray(seq, np.int32)[None]), H)
        out.append(int(jnp.argmax(logits[0, -1])))
        seq.append(out[-1])
    return out


def single_stream_state(params, prompt, n_decode):
    """K/V rows the single-stream path leaves: unpadded prefill, then
    ``n_decode`` one-token steps. Returns (kcache, vcache, rows)."""
    logits, kc, vc, pos = causal_lm.lm_prefill(
        params, jnp.asarray(np.asarray(prompt, np.int32)[None]), H, MAXLEN)
    tok = int(jnp.argmax(logits[0]))
    for _ in range(n_decode):
        logits, kc, vc, pos = causal_lm.lm_decode_step(
            params, jnp.asarray([[tok]], jnp.int32), kc, vc, pos, H)
        tok = int(jnp.argmax(logits[0]))
    return np.asarray(kc), np.asarray(vc), int(pos[0])


def test_mixed_lengths_empty_slot_and_midchunk_finish(params):
    """Slots at different lengths, one slot that never holds a request
    and one request that finishes in the middle of a chunk: every stream
    decodes token for token like ``lm_forward``, the rows left in the
    stores are the single-stream path's, and the empty slot's store is
    never written."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, V, n).astype(np.int32) for n in (3, 11, 20)]
    new = [10, 6, 12]            # 6: one from the prefill, 4 + 1 decoded
    eng = LMEngine(params, H, MAXLEN, n_slots=4, chunk=4)
    rids = [eng.submit(p, max_new=m) for p, m in zip(prompts, new)]
    eng.step_iteration()
    took = {rid: slot
            for rid, slot, _ in eng.recent_steps()[0]["admitted"]}
    slots = [took[rid] for rid in rids]
    assert sorted(slots) == [0, 1, 2]
    res = eng.run()
    for rid, p, m in zip(rids, prompts, new):
        assert res[rid] == forward_generate(params, p, m)
    kcs, vcs = np.asarray(eng._kc), np.asarray(eng._vc)
    for slot, p, m in zip(slots, prompts, new):
        # the last token was never fed back: prompt + (m - 1) rows
        kc1, vc1, rows = single_stream_state(params, p, m - 1)
        assert rows == p.size + m - 1
        np.testing.assert_allclose(kcs[slot, :, :rows], kc1[:, :rows],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(vcs[slot, :, :rows], vc1[:, :rows],
                                   rtol=1e-5, atol=1e-6)
    assert not kcs[3].any() and not vcs[3].any()


def test_kv_rows_attended_counts_grain_rounded_lengths():
    """``stats['kv_rows_attended']``: each active slot's position at each
    step of each chunk, rounded up to the attention kernel's tail grain;
    an empty slot counts nothing, a request that ends mid-chunk counts to
    the chunk's end."""
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    max_len = 320
    assert da.kv_block(max_len) == 160 and da.read_grain(320) == 32
    p = causal_lm.init_causal_lm(jax.random.PRNGKey(3), V, D, H, L, max_len)
    rng = np.random.default_rng(4)
    eng = LMEngine(p, H, max_len, n_slots=3, chunk=4)
    assert eng.stats["kv_rows_attended"] == 0
    eng.submit(rng.integers(0, V, 150).astype(np.int32), max_new=21)
    eng.submit(rng.integers(0, V, 10).astype(np.int32), max_new=3)
    eng.run()
    # the long request: 20 steps at positions 150..169, of which 150..160
    # read 160 rows (five grains) and 161..169 one grain more; the short
    # one holds its slot for its first chunk's 4 steps (positions
    # 10..13), one grain each. The
    # prompts take their windows' lane steps first, and the long request
    # decodes in the last of them, beside the short prompt's window: a
    # slot that is being prefilled reads nothing
    lane_steps = -(-150 // LANE_ROWS) + 1
    assert eng.stats["lane_steps"] == lane_steps
    assert eng.stats["decode_steps"] == lane_steps + 19
    assert eng.stats["kv_rows_attended"] \
        == 11 * 160 + 9 * 192 + 4 * 32
