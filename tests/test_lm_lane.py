"""The prompt lane of the contiguous ``LMEngine``: prompts prefilled
inside the decode chunks, a window of ``LANE_ROWS`` rows a step under the
decode rows (serving/lm_engine.py ``_chunk_scan``, ``_plan_lane``;
models/causal_lm.py ``_lm_window``).

Contract: tokens through the lane are the whole-prompt path's (and
``lm_forward``'s greedy tokens), the K/V rows it leaves are
``lm_prefill``'s, a slot that is empty or still being prefilled is written
by its own windows only, and a prompt whose last window ends inside a
chunk decodes from the next step. A module of its own, so that its
executables are dropped apart from ``test_lm_serving``'s (conftest).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

V, D, H, L = 97, 32, 4, 2

LANE_MAXLEN = 4 * LANE_ROWS


@pytest.fixture(scope="module")
def lane_params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(11), V, D, H, L, LANE_MAXLEN)


def forward_generate(params, prompt, max_new):
    """Greedy tokens from the full causal forward, one ``lm_forward`` a
    token, no cache at all (a compile a length: kept to a few tokens)."""
    seq = [int(t) for t in prompt]
    for _ in range(max_new):
        logits = causal_lm.lm_forward(
            params, jnp.asarray(np.asarray(seq, np.int32)[None]), H)
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def lane_prompt(n, seed):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def prefill_rows(params, prompt):
    """K and V as the whole-prompt ``lm_prefill`` leaves them."""
    _, kc, vc, _ = causal_lm.lm_prefill(
        params, jnp.asarray(prompt[None]), H, LANE_MAXLEN)
    return np.asarray(kc)[:, :prompt.size], np.asarray(vc)[:, :prompt.size]


_prefill_jit = jax.jit(causal_lm.lm_prefill, static_argnums=(2, 3))
_step_jit = jax.jit(causal_lm.lm_decode_step, static_argnums=(5,))


def lane_isolated(params, prompt, max_new):
    """``isolated_generate`` at the lane tests' capacity, its two programs
    jitted once: the whole-prompt prefill, then one-token steps (what
    ``tests/test_lm_serving.py`` holds to ``lm_forward``)."""
    logits, kc, vc, pos = _prefill_jit(
        params, jnp.asarray(prompt[None]), H, LANE_MAXLEN)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < max_new:
        logits, kc, vc, pos = _step_jit(
            params, jnp.asarray([[out[-1]]], jnp.int32), kc, vc, pos, H)
        out.append(int(jnp.argmax(logits[0])))
    return out


def assert_rows_close(got, want):
    """Within 1e-6 of the rows' own scale."""
    assert np.max(np.abs(got - want)) <= 1e-6 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("t", [
    5,                  # shorter than a window
    LANE_ROWS - 1, LANE_ROWS, LANE_ROWS + 1,
    2 * LANE_ROWS + 22,  # several windows in one chunk
    3 * LANE_ROWS + 9,  # four windows: two chunks of two steps
])
def test_lane_tokens_match_forward(lane_params, t):
    """Tokens of a prompt prefilled through the lane are ``lm_forward``'s
    greedy tokens, and the rows it left are ``lm_prefill``'s."""
    prompt = lane_prompt(t, seed=t)
    eng = LMEngine(lane_params, H, LANE_MAXLEN, n_slots=2, chunk=2)
    assert eng._lane
    rid = eng.submit(prompt, max_new=3)
    eng.step_iteration()
    windows = -(-t // LANE_ROWS)
    # the first chunk carries min(2, windows) windows and no decode row;
    # the chunk behind it is in flight when it is read, and where the
    # prompt ended in it, its first token waits for that chunk's tokens
    assert eng.recent_steps()[0]["lane_steps"] == min(2, windows)
    assert eng.progress(rid) == [] and eng._flight is not None
    eng.step_iteration()
    assert len(eng.progress(rid)) == (1 + 2 if windows <= 2 else 0)
    got = eng.run()[rid]
    assert got == lane_isolated(lane_params, prompt, 3)
    assert got[:2] == forward_generate(lane_params, prompt, 2)
    st = eng.stats
    assert (st["lane_steps"], st["lane_rows"], st["lane_tokens"]) \
        == (windows, windows * LANE_ROWS, t)
    k, v = prefill_rows(lane_params, prompt)
    assert_rows_close(np.asarray(eng._kc)[0, :, :t], k)
    assert_rows_close(np.asarray(eng._vc)[0, :, :t], v)
    # the other slot never held a request: nothing wrote it
    assert not np.asarray(eng._kc)[1].any()


def test_lane_two_prompts_queued_while_streams_decode(lane_params):
    """Two prompts taken in one iteration follow each other through the
    lane while an older stream decodes; the first to end joins the active
    set at the next step of the same chunk, the second's slot is written
    by its own windows only while it waits."""
    old = lane_prompt(7, seed=1)
    a, b = lane_prompt(LANE_ROWS + LANE_ROWS // 2, seed=2), \
        lane_prompt(3 * LANE_ROWS + 8, seed=3)    # two windows and four
    eng = LMEngine(lane_params, H, LANE_MAXLEN, n_slots=4, chunk=4)
    r_old = eng.submit(old, max_new=30)
    eng.step_iteration()            # its one lane step read, a chunk ahead
    eng.step_iteration()            # the chunk of 4 read, the next ahead
    assert len(eng.progress(r_old)) == 1 + 4
    ra, rb = eng.submit(a, max_new=12), eng.submit(b, max_new=6)

    def step():
        eng.step_iteration()
        rec = eng.recent_steps()[-1]
        return (rec["chunk"], rec["lane_steps"], rec["lane_tokens"],
                rec["active"]), [len(eng.progress(r))
                                 for r in (r_old, ra, rb)]

    # the two are admitted and their six windows planned into the chunk
    # behind the one in flight, which is read: the old stream's four
    assert step() == ((4, 0, 0, 1), [9, 0, 0])
    assert eng.recent_steps()[-1]["admitted"] == [[ra, 1], [rb, 2]]
    assert eng.slot_of(rb) == 2
    # in flight: a chunk of four lane steps. a's two windows, then it
    # decodes in steps 2 and 3 of the same chunk; b's first two windows
    kc = np.asarray(eng._kc)
    kb, _ = prefill_rows(lane_params, b)
    assert_rows_close(kc[2, :, :2 * LANE_ROWS], kb[:, :2 * LANE_ROWS])
    # the decode steps of that chunk left b's slot alone
    assert not kc[2, :, 2 * LANE_ROWS:].any() and not kc[3].any()
    # it is read (a's first token and the two behind it wait for the
    # next chunk's), and b's last two windows follow: a lane chunk of two
    # steps (not floored to a power of two: its length is data)
    assert step() == ((4, 4, a.size + 2 * LANE_ROWS, 1), [13, 0, 0])
    assert step() == ((2, 2, b.size - 2 * LANE_ROWS, 2), [15, 1 + 2 + 2, 0])
    # b's first token comes with the chunk behind it
    assert step() == ((4, 0, 0, 3), [19, 9, 1 + 4])
    assert step() == ((4, 0, 0, 3), [23, 12, 6])
    res = eng.run()
    for rid, p, m in ((r_old, old, 30), (ra, a, 12), (rb, b, 6)):
        assert res[rid] == lane_isolated(lane_params, p, m)
    st = eng.stats
    assert st["lane_tokens"] == old.size + a.size + b.size
    assert st["lane_steps"] == 1 + 2 + 4
    # slots x steps = decode tokens kept + wasted, the lane's first
    # tokens apart
    assert eng.n_slots * st["decode_steps"] == \
        (st["tokens_out"] - st["prefills"]) + st["wasted_slot_steps"]
    np.testing.assert_array_equal(
        np.asarray(eng._pos)[:3, 0], np.asarray(eng._pos_host)[:3])
    # one lane program served chunks of 1, 4 and 2 steps
    assert {k for k in eng._seen_programs if k[0] == "lane"} \
        == {("lane", 4)}


def test_lane_last_window_mid_chunk_joins_at_the_next_step(lane_params):
    """The chunk program itself: a prompt whose last window is step 1 of
    a lane chunk gets its first token in that step's ``outs``, and its
    slot decodes in steps 2 and 3 of the same loop, at the positions and
    from the rows a whole-prompt prefill would have left; a second
    prompt's window follows in the lane meanwhile. Of the plan's six
    rows the count says four run."""
    from nnstreamer_tpu.serving.lm_engine import _decode_chunk

    a, b = lane_prompt(LANE_ROWS + 9, seed=5), lane_prompt(20, seed=6)
    S, hd = 3, D // H
    plan = np.zeros((6, 4 + LANE_ROWS), np.int32)
    plan[4:, :4] = (0, 0, LANE_ROWS, 1)     # never run
    plan[0, :4], plan[0, 4:] = (1, 0, LANE_ROWS, 0), a[:LANE_ROWS]
    plan[1, :4], plan[1, 4:13] = (1, LANE_ROWS, 9, 1), a[LANE_ROWS:]
    plan[2, :4], plan[2, 4:24] = (2, 0, 20, 1), b
    plan[3, :4] = (0, 0, 0, 0)      # slot 0 never decodes here
    shape = (S, L * H, LANE_MAXLEN, hd)
    tokens, kc, vc, pos, outs, conf = _decode_chunk(
        lane_params, jnp.zeros((S, 1, 1), jnp.int32),
        jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
        jnp.zeros((S, 1), jnp.int32), np.zeros((S,), bool),
        np.zeros((S, 2), np.uint32), np.zeros((S,), np.float32),
        np.zeros((S,), np.int32), np.ones((S,), np.float32),
        (plan, np.int32(4)), n_heads=H, n_steps=6)
    outs = np.asarray(outs)
    assert outs.shape == (S, 6) and not outs[:, 4:].any()
    want_a = lane_isolated(lane_params, a, 3)
    assert [outs[1, 1], outs[1, 2], outs[1, 3]] == want_a
    assert outs[2, 2] == lane_isolated(lane_params, b, 1)[0]
    # a: its prompt and two decoded rows; b: its prompt, then one step
    np.testing.assert_array_equal(np.asarray(pos)[1:, 0],
                                  [a.size + 2, b.size + 1])
    ka, _ = prefill_rows(lane_params, a)
    assert_rows_close(np.asarray(kc)[1, :, :a.size], ka)
    assert np.asarray(kc)[1, :, a.size:a.size + 2].any()
    conf = np.asarray(conf)
    assert not conf[0].any() and not conf[3:].any()
    assert 0.0 < conf[1, 1] <= 1.0 and 0.0 < conf[2, 1] <= 1.0


@pytest.mark.parametrize("t,new", [(LANE_MAXLEN, 1), (LANE_MAXLEN - 3, 4),
                                   (LANE_ROWS, LANE_MAXLEN - LANE_ROWS + 1)])
def test_lane_capacity_edge(lane_params, t, new):
    """``prompt + max_new - 1 == max_len``: the last window ends at the
    store's last row, the last decode step writes it."""
    prompt = lane_prompt(t, seed=40 + new)
    eng = LMEngine(lane_params, H, LANE_MAXLEN, n_slots=2, chunk=4)
    rid = eng.submit(prompt, max_new=new)
    got = eng.run()[rid]
    assert got == lane_isolated(lane_params, prompt, new)
    assert not np.isnan(np.asarray(eng._kc)).any()
    with pytest.raises(ValueError):
        eng.submit(prompt, max_new=new + 1)


def test_lane_is_what_the_engine_is(lane_params):
    """The contiguous, continuous, chunked engine over a store that whole
    windows tile admits through the lane; the others prefill whole
    prompts, and give the same tokens."""
    prompt = lane_prompt(LANE_ROWS + 5, seed=9)
    want = lane_isolated(lane_params, prompt, 6)
    kinds = {"lane": {}, "spec": {"spec_draft": 2},
             "paged": {"kv_page_size": 16}}
    for name, kw in kinds.items():
        eng = LMEngine(lane_params, H, LANE_MAXLEN, n_slots=2, chunk=4, **kw)
        assert eng._lane == (name == "lane")
        rid = eng.submit(prompt, max_new=6)
        assert eng.run()[rid] == want, name
        assert (eng.stats["lane_steps"] > 0) == (name == "lane")
    odd = LMEngine(lane_params, H, LANE_MAXLEN - 8, n_slots=2, chunk=4)
    assert not odd._lane
    rid = odd.submit(prompt, max_new=6)
    assert odd.run()[rid] == want
