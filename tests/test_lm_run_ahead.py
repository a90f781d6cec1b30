"""The order of dispatch and read inside an iteration of ``LMEngine``
(serving/lm_engine.py ``_decode``): the contiguous engine with the prompt
lane dispatches chunk k + 1 before it reads chunk k; every other engine
keeps dispatch, read, retire.

Contract: the tokens are those of the same engine in the old order, whatever
ends a request and whenever it arrives; slots x steps = kept + wasted holds
after every iteration, with the columns a run-ahead chunk decoded for a
request that an EOS had already ended counted as waste; a first token is
handed out with the chunk behind it; ``_drain()`` makes the state a caller
outside the loop sees the old order's; a quiet engine has nothing in flight.
The product has no switch for the order: the tests that need the old one
override the private predicate ``_runs_ahead`` on their engine. A module of
its own, so that its executables are dropped apart from the others'
(conftest).
"""

import numpy as np
import pytest

import jax

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.sched import DeviceEngine
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

V, D, H, L = 97, 32, 4, 2
MAXLEN = 4 * LANE_ROWS
SLOTS, CHUNK = 2, 4
PS = 16             # page size of the paged engines


@pytest.fixture(scope="module")
def params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(5), V, D, H, L, MAXLEN)


def engine(params, ahead=None, **kw):
    """The engine; ``ahead`` False or True forces the order on it."""
    eng = LMEngine(params, H, MAXLEN, n_slots=SLOTS, chunk=CHUNK, **kw)
    if ahead is not None:
        eng._runs_ahead = lambda: ahead
    return eng


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def req(at, n, max_new, which=0, **kw):
    """An arrival: submitted before iteration ``at``; ``which`` picks the
    prompt among those of ``n`` tokens."""
    return at, dict(prompt=prompt(n, 100 * n + which), max_new=max_new, **kw)


def kept_tokens(reqs):
    """Decode tokens read so far: all but each request's first."""
    return sum(max(len(r.out) + len(r.held) - 1, 0) for r in reqs)


def lane_holds(eng, reqs):
    """What holds after every iteration of the engine with the lane, in
    either order."""
    st = eng.stats
    assert eng.n_slots * st["decode_steps"] \
        == kept_tokens(reqs) + st["wasted_slot_steps"]
    assert st["chunks_ahead"] <= st["chunks"]
    for r in reqs:
        # a first token alone is the whole of what was asked for, or
        # ended the request: otherwise it comes with the chunk behind
        assert len(r.out) != 1 or r.done
        assert r.due >= 0 and (not r.done or not r.held)


def nothing_in_flight(eng, reqs):
    assert eng._flight is None


def drive(eng, arrivals, each=lane_holds):
    """Offer ``arrivals`` by iteration and step until all is done, idle
    iterations included; after every iteration hold the engine to
    ``each``. Returns the requests, in the order offered."""
    todo, reqs, it = sorted(arrivals, key=lambda a: a[0]), [], 0
    while todo or eng.pending():
        while todo and todo[0][0] <= it:
            eng.submit(**todo.pop(0)[1])
            reqs.append(eng._queue[-1])
        more = eng.step_iteration()
        it += 1
        assert it < 400
        each(eng, reqs)
        if not more:
            assert eng._flight is None and eng.pending() == 0
    assert eng._flight is None
    assert all(r.due == 0 and r.done for r in reqs)
    return reqs


def first_seen_at(params, idx, n=5, **kw):
    """A prompt of ``n`` tokens and the token its sampled stream makes at
    index ``idx`` for the first time there: an EOS that hits that step."""
    for seed in range(40):
        p = prompt(n, 100 * n + seed)
        eng = engine(params)
        rid = eng.submit(p, 12, **kw)
        out = eng.run()[rid]
        if out[idx] not in out[:idx]:
            return seed, out[idx]
    raise AssertionError("no seed gives a fresh token there")


SAMPLED = dict(temperature=1.0, top_k=12, seed=9)


def mix(seed, sampled):
    """Six requests of seeded lengths, two of them waiting for a slot."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(6):
        kw = dict(SAMPLED, seed=int(rng.integers(1 << 20))) \
            if sampled[i % len(sampled)] else {}
        out.append(req(int(rng.integers(0, 6)), int(rng.integers(1, 90)),
                       int(rng.integers(1, 14)), seed + i, **kw))
    return out


def eos_case(idx):
    """A sampled stream that an EOS ends at token ``idx`` of 12 (a lone
    prompt of one window: token 0 in the lane's chunk, 1-4 and 5-8 in
    the chunks behind), beside a long stream and a prompt that waits for
    the slot the EOS frees."""
    def build(params):
        seed, eos = first_seen_at(params, idx, **SAMPLED)
        return [req(0, 5, 12, seed, eos=eos, **SAMPLED),
                req(0, 70, 30), req(1, 130, 5)]
    return build


#: name -> (the arrivals, or what builds them from the weights; whether
#: an EOS ends a request that the chunk ahead still decodes)
CASES = {
    "greedy_mix_0": (mix(0, [False]), False),
    "greedy_mix_1": (mix(1, [False]), False),
    "greedy_mix_2": (mix(2, [False]), False),
    "sampled_mix_0": (mix(3, [True]), False),
    "greedy_and_sampled_mix": (mix(4, [True, False]), False),
    "eos_first_token": (eos_case(0), True),
    "eos_mid_chunk": (eos_case(2), True),
    "eos_last_step_of_a_chunk": (eos_case(4), True),
    "eos_mid_second_chunk": (eos_case(6), True),
    "max_new_1": ([req(0, 9, 1), req(0, 8, 20), req(1, 70, 1),
                   req(2, 3, 1)], False),
    "max_new_2": ([req(0, 9, 2), req(0, 8, 20), req(1, 70, 2),
                   req(2, 3, 2)], False),
    "prompt_1": ([req(0, 1, 7), req(0, 20, 9)], False),
    "prompt_63": ([req(0, LANE_ROWS - 1, 7), req(1, 20, 9)], False),
    "prompt_64": ([req(0, LANE_ROWS, 7), req(1, 20, 9)], False),
    "prompt_65": ([req(0, LANE_ROWS + 1, 7), req(1, 20, 9)], False),
    "prompt_200": ([req(0, 200, 7), req(0, 20, 19), req(3, 190, 3)], False),
    "slot_at_capacity": ([req(0, MAXLEN - 10, 11), req(0, 8, 40),
                          req(2, MAXLEN, 1)], False),
    "arrivals_between_iterations": (
        [req(i, 5 + 9 * i, 4 + i % 5, i) for i in range(9)], False),
    "idle_and_started_again": (
        [req(0, 9, 6), req(1, 40, 3), req(40, 70, 9), req(41, 5, 2),
         req(80, 12, 5)], False),
}


@pytest.mark.parametrize("name", CASES)
def test_tokens_are_the_old_orders(params, name):
    arrivals, eos = CASES[name]
    if callable(arrivals):
        arrivals = arrivals(params)
    ahead, old = engine(params), engine(params, ahead=False)
    assert ahead._runs_ahead()
    got = [r.out for r in drive(ahead, arrivals)]
    want = [r.out for r in drive(old, arrivals)]
    assert got == want
    assert all(1 <= len(g) <= a[1]["max_new"]
               for g, a in zip(got, sorted(arrivals, key=lambda a: a[0])))
    st = ahead.stats
    assert 0 < st["chunks_ahead"] < st["chunks"]
    assert (st["ahead_dropped_slot_steps"] > 0) == eos
    assert old.stats["chunks_ahead"] == 0
    assert old.stats["ahead_dropped_slot_steps"] == 0
    assert st["tokens_out"] == old.stats["tokens_out"]
    assert st["lane_tokens"] == old.stats["lane_tokens"]
    # the host's mirror of the positions is the device's, in flight or not
    np.testing.assert_array_equal(
        np.asarray(ahead._pos)[:, 0], np.asarray(ahead._pos_host))


@pytest.mark.parametrize("kind", ["speculative", "enrolled", "paged",
                                  "whole_prompt"])
def test_the_other_engines_keep_the_old_order(params, kind):
    """Speculative windows are drafted from the last tokens, a tenant's
    call is the scheduler's unit of account, and the engines that prefill
    whole prompts block in every admission: none runs ahead, and each
    gives the lane engine's greedy tokens."""
    arrivals = mix(7, [False])
    want = [r.out for r in drive(engine(params), arrivals)]
    sched = None
    if kind == "whole_prompt":      # a store that windows do not tile
        eng = LMEngine(params, H, MAXLEN - 8, n_slots=SLOTS, chunk=CHUNK)
    else:
        eng = engine(params, **{"speculative": {"spec_draft": 2},
                                "paged": {"kv_page_size": PS},
                                "enrolled": {}}[kind])
    if kind == "enrolled":
        sched = DeviceEngine("t", autostart=True)
        eng.enroll(sched, name="srv")
    try:
        assert not eng._runs_ahead()
        reqs = drive(eng, arrivals, each=nothing_in_flight)
    finally:
        if sched is not None:
            eng.unenroll()
            sched.stop()
    assert [r.out for r in reqs] == want
    st = eng.stats
    assert st["chunks"] > 0 and st["chunks_ahead"] == 0
    assert st["ahead_dropped_slot_steps"] == 0


def test_enrolling_reads_what_is_in_flight(params):
    """An engine that ran ahead has nothing in flight once it is a
    tenant, and again runs ahead once it is none."""
    eng = engine(params)
    rid = eng.submit(prompt(9, 1), 14)
    eng.step_iteration()
    assert eng._flight is not None
    sched = DeviceEngine("t", autostart=True)
    try:
        eng.enroll(sched, name="srv")
        assert eng._flight is None and not eng._runs_ahead()
        eng.step_iteration()
        assert eng._flight is None
        eng.unenroll()
    finally:
        sched.stop()
    eng.step_iteration()
    assert eng._flight is not None
    want = engine(params, ahead=False)
    w = want.submit(prompt(9, 1), 14)
    assert eng.run()[rid] == want.run()[w]


@pytest.mark.parametrize("t,max_new,seen", [
    # the lane's chunk is read in the first iteration, the chunk behind
    # it in the second, and so on: tokens visible after each
    (5, 9, [0, 1 + 4, 9]),
    (5, 2, [0, 2]),
    (5, 1, [1]),                    # nothing is behind it
    (LANE_ROWS + 3, 6, [0, 1 + 4, 6]),   # two windows, one chunk of two
    (5 * LANE_ROWS // 2, 3, [0, 3]),     # three windows: the third is step
                                         # 2 of 3, and no step is behind it
])
def test_a_first_token_comes_with_the_chunk_behind_it(params, t, max_new,
                                                      seen):
    eng = LMEngine(params, H, MAXLEN, n_slots=SLOTS, chunk=CHUNK)
    rid = eng.submit(prompt(t, 3), max_new)
    got = []
    while True:
        more = eng.step_iteration()
        got.append(len(eng.progress(rid)))
        if not more:
            break
    assert got == seen
    assert eng._flight is None


def test_an_idle_engine_dispatches_two_and_reads_one(params):
    """With nothing in flight an iteration dispatches, dispatches the
    next and reads the first; in a steady state it dispatches one and
    reads one; the last chunk is only read."""
    eng = engine(params)
    eng.submit(prompt(5, 1), 10)
    calls = []
    run_chunk = LMEngine._run_chunk
    eng._run_chunk = lambda n: calls.append(n) or run_chunk(eng, n)
    per_iteration = []
    while True:
        before = len(calls)
        more = eng.step_iteration()
        per_iteration.append((len(calls) - before,
                              eng.recent_steps()[-1]["chunk"]))
        if not more:
            break
    # 1 lane step, then 9 tokens behind the first: chunks of 4, 4, 1
    assert per_iteration == [(2, 1), (1, 4), (1, 4), (0, 1)]
    assert eng.stats["chunks"] == 4 and eng.stats["chunks_ahead"] == 3


def paged(params, ahead):
    return engine(params, ahead=ahead, kv_page_size=PS, kv_pages=40)


def assert_docs_equal(got, want):
    assert (got is None) == (want is None)
    if got is None:
        return
    assert {k: v for k, v in got.items() if k != "entries"} \
        == {k: v for k, v in want.items() if k != "entries"}
    assert len(got["entries"]) == len(want["entries"])
    for a, b in zip(got["entries"], want["entries"]):
        assert a["key"] == b["key"]
        np.testing.assert_array_equal(a["k"], b["k"])
        np.testing.assert_array_equal(a["v"], b["v"])


@pytest.mark.parametrize("call", ["export_session", "freeze_session",
                                  "checkpoint_session", "kv_stats",
                                  "prefill_and_export"])
def test_a_drain_comes_before_every_look_from_outside(params, call):
    """No engine that exports pages runs ahead yet; one made to (the
    order the lane over pages will bring) has the session's last chunk
    in flight when a caller outside the loop looks: ``_drain()`` first
    makes what it sees what the old order shows."""
    p = prompt(2 * PS + 5, 2)
    other = prompt(3 * PS + 1, 4)

    def look(ahead):
        eng = paged(params, ahead)
        rid = eng.submit(p, 6, session="s")
        if ahead:
            eng.step_iteration()        # 4 of 5 steps read, 1 in flight
            assert eng._flight is not None and rid not in eng.results
        else:
            eng.run()
        if call == "prefill_and_export":
            first, doc = eng.prefill_and_export(other)
            return eng, (first, eng.results[rid]), doc
        if call == "kv_stats":
            return eng, eng.kv_stats, None
        if call == "checkpoint_session":
            path, doc = eng.checkpoint_session("s")
            return eng, list(path), doc
        if call == "freeze_session":
            return eng, eng.freeze_session("s"), None
        return eng, None, eng.export_session("s")

    eng, got, got_doc = look(True)
    assert eng._flight is None and eng.pending() == 0
    _, want, want_doc = look(False)
    if call == "kv_stats":
        # pages are counted when taken: the order takes them sooner
        got, want = ({k: v for k, v in d.items() if k != "pages_peak"}
                     for d in (got, want))
    assert got == want
    assert_docs_equal(got_doc, want_doc)
    if call in ("export_session", "checkpoint_session"):
        assert got_doc is not None
