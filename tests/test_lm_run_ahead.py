"""The order of dispatch and read inside an iteration of ``LMEngine``
(serving/lm_engine.py ``_decode``): the contiguous engine with the prompt
lane dispatches chunk k + 1 before it reads chunk k; every other engine
keeps dispatch, read, retire.

Contract: the tokens are those of the same engine in the old order, whatever
ends a request and whenever it arrives; slots x steps = kept + wasted holds
after every iteration, with the columns a run-ahead chunk decoded for a
request that an EOS had already ended counted as waste; a request that a
dispatched chunk ends by length gives its slot to the queue's head for the
chunk right behind that one, and is seen running (``pending``,
``slot_of``, ``progress``) until that chunk is read, where an EOS frees a
slot only once it is read; a first token is handed out with the chunk
behind it; ``_drain()`` makes the state a caller outside the loop sees the
old order's; a quiet engine has nothing in flight. While a request waits
in the queue, a chunk that the engine running ahead dispatches ends no
later than the step in which a decoding request ends by length, with no
executable the tails did not use and no token changed; with an empty
queue, or an end by EOS, chunk lengths are those of the rule before.
The product has no switch for the order: the tests that need the old one
override the private predicate ``_runs_ahead`` on their engine, and those
that need the lengths before the cut hide the queue from the planning of
each chunk (``uncut``). A module of its own, so that its executables are
dropped apart from the others' (conftest).
"""

from collections import deque

import numpy as np
import pytest

import jax

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.obs import tracing
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


def admissions(eng):
    """rid -> (slot, ahead) from the step records."""
    return {rid: (slot, ahead) for rec in eng.recent_steps()
            for rid, slot, ahead in rec["admitted"]}


def lane_holds(eng, reqs):
    """What holds after every iteration of the engine with the lane, in
    either order."""
    st = eng.stats
    assert eng.n_slots * st["decode_steps"] \
        == kept_tokens(reqs) + st["wasted_slot_steps"]
    assert st["chunks_ahead"] <= st["chunks"]
    assert st["chunks_cut"] <= st["chunks"]
    assert st["admits_ahead"] == sum(a for _, a in admissions(eng).values())
    assert eng.pending() == sum(not r.done for r in reqs)
    queued = {r.rid for r in eng._queue}
    for r in reqs:
        # a first token alone is the whole of what was asked for, or
        # ended the request: otherwise it comes with the chunk behind
        assert len(r.out) != 1 or r.done
        assert r.due >= 0 and (not r.done or not r.held)
        # running to whoever looks until its last chunk is read, even
        # where its slot holds the next request by now
        running = not r.done and r.rid not in queued
        assert (eng.slot_of(r.rid) is not None) == running
        assert eng.progress(r.rid) == (r.out if r.rid not in queued else [])


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
    "queue_behind_full_slots": (
        [req(0, 5 + 7 * i, 3 + 2 * i, i) for i in range(6)], False),
    "queue_behind_full_slots_sampled": (
        [req(0, 5 + 7 * i, 3 + 2 * i, i, **dict(SAMPLED, seed=i))
         for i in range(6)], False),
    "one_token_answers_in_a_queue": (
        [req(0, 20, 14)] + [req(0, 9, 1, i) for i in range(5)], False),
}

#: the cases whose queue waits for slots that chunks end by length: some
#: of their admissions come ahead of the read of such a chunk
QUEUED = ("queue_behind_full_slots", "queue_behind_full_slots_sampled",
          "one_token_answers_in_a_queue")


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
    assert old.stats["admits_ahead"] == 0
    assert old.stats["chunks_cut"] == 0
    if name in QUEUED:
        assert st["admits_ahead"] > 0
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
    assert st["admits_ahead"] == 0 and not eng._released
    assert st["chunks_cut"] == 0
    assert not any(a for _, a in admissions(eng).values())


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


def test_the_wait_watches_the_chunk_behind(params, monkeypatch):
    """A chunk read while a full chunk behind it is in flight arms the
    witness with the outputs of that one; a chunk with a shorter one
    behind it (here the tail of one step), or with none (the last), arms
    it alone; every wait is split into the record's ``wait``."""
    armed = []
    arm = tracing._WITNESS.arm
    monkeypatch.setattr(tracing._WITNESS, "arm",
                        lambda t, awaited, behind: armed.append(
                            (awaited, behind)) or arm(t, awaited, behind))
    eng = engine(params)
    eng.submit(prompt(5, 1), 10)
    flights = []
    while True:
        before = len(armed)
        more = eng.step_iteration()
        flights.append(eng._flight)
        # one wait an iteration, on the chunk's tokens
        assert len(armed) == before + 1
        if not more:
            break
    behinds = [b for _, b in armed]
    assert [f and f.n for f in flights] == [CHUNK, CHUNK, 1, None]
    assert [b is None for b in behinds] \
        == [f is None or f.n < CHUNK for f in flights]
    assert all(b[0] is f.outs for b, f in zip(behinds, flights)
               if b is not None)
    for rec in eng.recent_steps():
        w = rec["wait"]
        parts = sum(w[f"{c}_s"] for c in tracing.WAIT_CAUSES)
        assert parts == pytest.approx(rec["phases"]["device_wait"],
                                      abs=1e-6)
        assert w["longest_s"] == pytest.approx(
            rec["phases"]["device_wait"], abs=1e-6)


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


def dispatch_log(eng):
    """Every chunk the engine dispatches, in order: (iteration, the rids it
    gives tokens to, the rids whose prompt windows ride it)."""
    log = []
    dispatch = eng._dispatch_chunk

    def logged(step, rec, ahead=False):
        waiting = {s: (eng._slot_req[s].rid, at)
                   for s, at in eng._lane_at.items()}
        chunk = dispatch(step, rec, ahead)
        if chunk is not None:
            log.append((rec["iteration"],
                        {req.rid for slot, (req, _, keeps) in
                         chunk.cols.items()
                         if keeps or slot in chunk.firsts},
                        {rid for s, (rid, at) in waiting.items()
                         if eng._lane_at.get(s) != at}))
        return chunk

    eng._dispatch_chunk = logged
    return log


@pytest.mark.parametrize("sampled", [False, True])
def test_the_queue_head_takes_a_slot_when_its_last_chunk_is_dispatched(
        params, sampled):
    """R's last two tokens ride the chunk dispatched in iteration 3, cut to
    two steps since N waits. That dispatch frees its slot: N, at the
    queue's head, takes it in iteration 4 while the chunk is unread, and
    N's first window rides the next chunk dispatched, right behind R's
    last, as in the order that reads each chunk before the next (where N
    is admitted after the read). Until the read R is running to whoever
    looks, from the slot N holds."""
    kw = dict(SAMPLED) if sampled else {}
    arrivals = [req(0, 5, 12, 1, **kw), req(0, 9, 40, 2, **kw),
                req(0, 6, 4, 3, **kw)]
    ahead, old = engine(params), engine(params, ahead=False)
    r, _, n = (ahead.submit(**a) for _, a in arrivals)
    for _, a in arrivals:
        old.submit(**a)
    logs = dispatch_log(ahead), dispatch_log(old)
    for _ in range(3):
        ahead.step_iteration()
    # released and unread: no slot holds R, yet R is running in slot 0
    assert ahead._slot_req[0] is None and ahead.slot_of(r) == 0
    assert ahead.pending() == 3 and len(ahead.progress(r)) == 10
    seen = []
    read = ahead._read_chunk

    def looked(chunk, step, rec):
        seen.append((ahead.slot_of(r), ahead.slot_of(n), ahead.pending(),
                     len(ahead.progress(r))))
        read(chunk, step, rec)

    ahead._read_chunk = looked
    ahead.step_iteration()
    assert seen == [(0, 0, 3, 10)]
    assert ahead.slot_of(r) is None and ahead.progress(r) == ahead.results[r]
    assert admissions(ahead)[n] == (0, True)
    assert ahead.stats["admits_ahead"] == 1
    assert ahead.stats["chunks_cut"] == 1
    got, want = ahead.run(), old.run()
    assert got == want
    assert admissions(old)[n] == (0, False) and old.stats["admits_ahead"] == 0
    for log in logs:
        last_r = max(i for i, (_, gets, _) in enumerate(log) if r in gets)
        first_n = min(i for i, (_, _, rides) in enumerate(log) if n in rides)
        assert first_n == last_r + 1
        assert log[first_n][0] == 4
    # slots x steps = kept + wasted
    st = ahead.stats
    kept = sum(len(out) - 1 for out in got.values())
    assert SLOTS * st["decode_steps"] == kept + st["wasted_slot_steps"]


@pytest.mark.parametrize("idx", [0, 2, 4])
def test_an_eos_frees_its_slot_when_it_is_read(params, idx):
    """An EOS in the chunk that is read while the chunk behind it (which
    would not end the request by length) is in flight: the host learns of
    it only at the read, so the slot is not given up at a dispatch, and
    the prompt waiting for it is admitted after that read, not ahead."""
    arrivals = eos_case(idx)(params)

    def each(eng, reqs):
        lane_holds(eng, reqs)
        assert reqs[0].rid not in eng._released

    eng = engine(params)
    reqs = drive(eng, arrivals, each=each)
    assert reqs[0].out[-1] == reqs[0].eos and len(reqs[0].out) == idx + 1
    assert admissions(eng)[reqs[2].rid] == (0, False)
    assert eng.stats["ahead_dropped_slot_steps"] > 0


def pow2_floor(n):
    return 1 << (n.bit_length() - 1)


def uncut(eng):
    """``eng`` planning every chunk as if no request waited in the queue:
    the chunk lengths of the rule before the cut, in the same order."""
    dispatch = eng._dispatch_chunk

    def hidden(step, rec, ahead=False):
        queue, eng._queue = eng._queue, deque()
        try:
            return dispatch(step, rec, ahead)
        finally:
            eng._queue = queue

    eng._dispatch_chunk = hidden
    return eng


def plan_log(eng):
    """Every chunk the engine dispatches, with what the host knew as it
    planned it: the iteration, whether a request waited in the queue, the
    tokens each decoding request had left to make by length (rid ->
    count), the length the rule before the cut gives, and the rids the
    dispatch released."""
    log = []
    dispatch = eng._dispatch_chunk

    def logged(step, rec, ahead=False):
        active = eng._decoding()
        left = {eng._slot_req[s].rid: LMEngine._left(eng._slot_req[s])
                for s in active}
        cap = eng._m_slot - max((eng._pos_host[s] for s in active),
                                default=0)
        lane = bool(eng._lane_at)
        if lane:
            windows = sum(-(-(eng._slot_req[s].prompt.size - at)
                            // LANE_ROWS) for s, at in eng._lane_at.items())
            old = min(CHUNK, cap, windows)
        else:
            old = pow2_floor(min(CHUNK, cap, max(left.values(), default=1)))
        queued = bool(eng._queue)
        chunk = dispatch(step, rec, ahead)
        if chunk is not None:
            log.append(dict(chunk=chunk, it=rec["iteration"], lane=lane,
                            queued=queued, left=left, old=old,
                            released={r for r in left if r in eng._released}))
        return chunk

    eng._dispatch_chunk = logged
    return log


def cuts_read(eng, log):
    """The chunks of ``log`` shorter than the rule before gives, of those
    read so far."""
    return sum(e["chunk"].n < e["old"] for e in log
               if e["chunk"] is not eng._flight)


#: every executable the engine may use: the lane's, and a plain chunk of
#: ``chunk`` steps or of a power of two below it
PROGRAMS = {("lane", CHUNK)} | {("chunk", 1 << k)
                                for k in range(CHUNK.bit_length())}


@pytest.mark.parametrize("name", CASES)
def test_a_chunk_ends_by_the_earliest_end_while_a_queue_waits(params, name):
    """With a request in the queue, a chunk ends no later than the step in
    which the first decoding request ends by length (a lane chunk there,
    a plain one at a power of two at or before it), and a chunk that ends
    a request ends at its last token; with an empty queue a chunk runs
    the length of the rule before. ``chunks_cut`` counts the chunks made
    shorter, slots x steps = kept + wasted after every iteration, the
    tokens are those of the lengths before, and no executable is new."""
    arrivals, _ = CASES[name]
    if callable(arrivals):
        arrivals = arrivals(params)
    eng, before = engine(params), uncut(engine(params))
    log, log_before = plan_log(eng), plan_log(before)

    def each(eng, reqs):
        lane_holds(eng, reqs)
        assert eng.stats["chunks_cut"] == cuts_read(eng, log)

    got = [r.out for r in drive(eng, arrivals, each=each)]
    assert got == [r.out for r in drive(before, arrivals)]
    for e in log:
        n = e["chunk"].n
        if e["queued"] and e["left"]:
            first = min(e["left"].values())
            cut = min(e["old"], first)
            assert n == (cut if e["lane"] else pow2_floor(cut))
            assert all(e["left"][rid] == n for rid in e["released"])
        else:
            assert n == e["old"]
    assert eng.stats["chunks_cut"] == sum(e["chunk"].n < e["old"]
                                          for e in log)
    assert before.stats["chunks_cut"] == 0
    # until a request waits, the lengths are the rule's before
    queued = next((i for i, e in enumerate(log)
                   if e["queued"] and e["left"]), len(log))
    assert [e["chunk"].n for e in log[:queued]] \
        == [e["chunk"].n for e in log_before[:queued]]
    assert eng._seen_programs <= PROGRAMS
    assert before._seen_programs <= PROGRAMS


def device_step(log, which):
    """The steps of the chunks of ``log`` before the one ``which`` picks,
    and those through it."""
    i = which(log)
    before = sum(e["chunk"].n for e in log[:i])
    return before, before + log[i]["chunk"].n


def ends(rid):
    """The entry of a log whose dispatch released ``rid``."""
    return lambda log: next(i for i, e in enumerate(log)
                            if rid in e["released"])


def last_window(log, rid):
    """The device step (from 0) whose lane window ended ``rid``'s
    prompt."""
    for i, e in enumerate(log):
        for slot, j in e["chunk"].firsts.items():
            if e["chunk"].cols[slot][0].rid == rid:
                return sum(f["chunk"].n for f in log[:i]) + j
    raise AssertionError(f"no window ended request {rid}")


@pytest.mark.parametrize("sampled", [False, True])
def test_a_plain_chunk_is_cut_to_powers_of_two_that_reach_the_end(
        params, sampled):
    """R has 3 tokens left to make when the chunk of iteration 3 is
    planned, while N waits: the chunks run 2 steps, then 1, and that one
    ends R at its last token and frees its slot (3 = 2 + 1), where the
    rule before runs 4 with a step of R's slot past its end. N takes the
    slot in the next iteration, and its first window rides the next chunk
    a step sooner on the device."""
    kw = dict(SAMPLED) if sampled else {}
    arrivals = [req(0, 5, 13, 1, **kw), req(0, 9, 40, 2, **kw),
                req(0, 6, 4, 3, **kw)]
    eng, before = engine(params), uncut(engine(params))
    logs = plan_log(eng), plan_log(before)
    reqs = drive(eng, arrivals)
    assert [r.out for r in reqs] == [r.out for r in drive(before, arrivals)]
    r, _, n = (q.rid for q in reqs)
    got = [[e["chunk"].n for e in log][:5] for log in logs]
    assert got == [[2, 4, 4, 2, 1], [2, 4, 4, 4, 1]]
    assert [e["it"] for e in logs[0]][:5] == [1, 1, 2, 3, 4]
    assert eng.stats["chunks_cut"] == 2
    # R's last token ends the chunk that releases it, a step sooner, and
    # N's window rides the chunk right behind, the lane's only one
    assert [device_step(log, ends(r))[1] for log in logs] == [13, 14]
    assert [last_window(log, n) for log in logs] == [13, 14]
    for log in logs:
        behind = log[ends(r)(log) + 1]
        assert behind["lane"] and behind["chunk"].n == 1
    assert admissions(eng)[n] == admissions(before)[n] == (0, True)


def test_a_lane_chunk_is_cut_at_the_end(params):
    """R ends 2 steps into a chunk whose lane has 4 of B's windows while N
    waits: the chunk runs 2 steps (a traced count: not floored), ends R
    at its last token and frees its slot; the lane runs the rest of B's
    windows and N's behind them in the next chunk. The lane takes its
    windows in order, so N's window lies where it did under the rule
    before; R's last token is read 2 steps sooner."""
    arrivals = [req(0, 5, 7, 1), req(1, 3 * LANE_ROWS + 58, 3, 2),
                req(1, 6, 4, 3)]
    eng, before = engine(params), uncut(engine(params))
    logs = plan_log(eng), plan_log(before)
    reqs = drive(eng, arrivals)
    assert [r.out for r in reqs] == [r.out for r in drive(before, arrivals)]
    r, b, n = (q.rid for q in reqs)
    cut, old = (log[ends(r)(log)] for log in logs)
    assert (cut["lane"], cut["queued"], cut["chunk"].n, cut["old"]) \
        == (True, True, 2, 4)
    assert (old["lane"], old["chunk"].n) == (True, 4)
    assert eng.stats["chunks_cut"] == 1
    assert [device_step(log, ends(r))[1] for log in logs] == [7, 9]
    assert [last_window(log, b) for log in logs] == [8, 8]
    assert [last_window(log, n) for log in logs] == [9, 9]
    assert eng._seen_programs <= PROGRAMS


@pytest.mark.parametrize("idx", [0, 2, 4, 6])
def test_an_eos_does_not_cut(params, idx):
    """A request an EOS ends at token ``idx`` of 12 while a prompt waits
    for a slot: every chunk dispatched until the EOS is read runs the
    length it runs for the same request without an EOS. The host cannot
    count an end the tokens decide, so it cuts nothing there."""
    seed, eos = first_seen_at(params, idx, **SAMPLED)
    arrivals = [req(0, 5, 12, seed, eos=eos, **SAMPLED), req(0, 70, 30),
                req(0, 130, 5)]
    plain = [(at, {k: v for k, v in kw.items() if k != "eos"})
             for at, kw in arrivals]
    done_at = []

    def each(eng, reqs):
        lane_holds(eng, reqs)
        if reqs[0].done and not done_at:
            done_at.append(eng.stats["iterations"])

    eng, other = engine(params), engine(params)
    logs = plan_log(eng), plan_log(other)
    reqs = drive(eng, arrivals, each=each)
    drive(other, plain)
    assert reqs[0].out[-1] == reqs[0].eos and len(reqs[0].out) == idx + 1
    got, want = ([(e["it"], e["chunk"].n) for e in log
                  if e["it"] <= done_at[0]] for log in logs)
    assert got == want
    assert any(e["queued"] for e in logs[0] if e["it"] <= done_at[0])
