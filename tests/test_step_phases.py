"""Step phases of ``LMEngine`` (obs/tracing.phase): the ``<phase>_s``
counters in ``LMEngine.stats``, the ``serving.*`` spans an iteration
writes through the profiler and (tracing on) into the span store, the
per-iteration records with their stall warning, and the read-only views
``progress`` / ``slot_of``. CPU, toy model; nothing here compares a time
with a threshold but the stall, which the test makes itself by sleeping
past the engine's own limit."""

import glob
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation
from jax.sharding import Mesh

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.obs import events as obs_events
from nnstreamer_tpu.obs import profile as obs_profile
from nnstreamer_tpu.obs import tracing
from nnstreamer_tpu.serving import LMEngine, TPLMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS, STEP_PHASES

V, D, H, L, MAXLEN = 37, 32, 4, 1, 64
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the phases directly under ``serving.step`` and under ``serving.admit``
STEP_CHILDREN = ("admit", "decode_dispatch", "decode_wait", "retire")
ADMIT_CHILDREN = ("admit_host", "prefill_dispatch", "slot_insert",
                  "first_token_wait")
#: the engine kinds: "lane" prefills inside its decode chunks, so its
#: admission is host work; "whole" (a store that windows of LANE_ROWS do
#: not tile) runs a whole-prompt prefill program at admission
KINDS = ("lane", "whole")


@pytest.fixture(scope="module")
def params():
    return causal_lm.init_causal_lm(
        jax.random.PRNGKey(3), V, D, H, L, MAXLEN)


def _engine(params, kind="lane", **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk", 4)
    eng = LMEngine(params, H, MAXLEN if kind == "lane" else MAXLEN - 16,
                   **kw)
    assert eng._lane == (kind == "lane" and not kw.get("spec_draft"))
    return eng


def _admit_children(kind):
    return ADMIT_CHILDREN[:1] if kind == "lane" else ADMIT_CHILDREN


def _phases(kind):
    return tuple(p for p in STEP_PHASES if p not in ADMIT_CHILDREN[1:]) \
        if kind == "lane" else STEP_PHASES


def _prompt(n, start=1):
    return (np.arange(start, start + n) % V).astype(np.int32)


def _drive(eng):
    """Step until idle; the number of iterations made."""
    n = 0
    while True:
        n += 1
        if not eng.step_iteration():
            return n


@pytest.fixture
def tracing_on():
    was = tracing.enabled()
    tracing.store().reset()
    tracing.enable()
    yield tracing.store()
    (tracing.enable if was else tracing.disable)()
    tracing.store().sample_every = 1
    tracing.store().reset()


@pytest.fixture
def events_on():
    ring = obs_events.ring()
    was = ring.is_enabled
    ring.reset()
    obs_events.enable()
    yield ring
    obs_events.disable()
    ring.reset()
    ring._enabled = was


# --------------------------------------------------------------------------- #
# counters
# --------------------------------------------------------------------------- #

def test_counts_match_what_was_driven(params):
    eng = _engine(params)
    for i in range(3):
        eng.submit(_prompt(5 + i), max_new=6)
    n = _drive(eng)
    st = eng.stats
    assert st["iterations"] == n
    assert st["prefills"] == 3
    # every iteration of this run read one chunk, and every chunk but
    # the idle engine's first was dispatched while the one before it was
    # unread (the lane's chunk of a prompt is a chunk like any other)
    assert st["chunks"] == n and st["chunks_ahead"] == n - 1
    assert st["decode_steps"] == sum(r["chunk"] for r in eng.recent_steps())
    # an idle iteration counts, and dispatches nothing
    eng.step_iteration()
    assert eng.stats["iterations"] == n + 1
    assert eng.stats["chunks"] == n and eng._flight is None
    assert "wall_s" not in st


@pytest.mark.parametrize("kind", KINDS)
def test_phase_counters_are_nonnegative_and_monotone(params, kind):
    eng = _engine(params, kind)
    seen = {k: 0.0 for k in eng.stats if k.endswith("_s")}
    assert {f"{p}_s" for p in STEP_PHASES} <= set(seen)
    for i in range(4):
        eng.submit(_prompt(4 + 3 * i), max_new=5 + i)
        while eng.step_iteration():
            for k, was in seen.items():
                assert eng.stats[k] >= was >= 0.0, k
                seen[k] = eng.stats[k]
    # an admission through the lane dispatches, inserts and awaits nothing
    assert {p for p in STEP_PHASES if eng.stats[f"{p}_s"] > 0.0} \
        == set(_phases(kind))


def test_children_sum_to_no_more_than_their_parent(params):
    eng = _engine(params)
    for i in range(5):
        eng.submit(_prompt(3 + 2 * i), max_new=7)
    eng.run()
    st = eng.stats
    assert sum(st[f"{p}_s"] for p in STEP_CHILDREN) <= st["step_s"]
    assert sum(st[f"{p}_s"] for p in ADMIT_CHILDREN) <= st["admit_s"]
    for rec in eng.recent_steps():
        ph = rec["phases"]
        assert sum(ph[p] for p in STEP_CHILDREN) <= ph["step"] + 1e-9
        assert ph["step"] == pytest.approx(rec["wall_s"])


def test_first_use_and_admission_wait(params):
    eng = _engine(params, n_slots=1)
    eng.submit(_prompt(5), max_new=4)
    eng.submit(_prompt(6), max_new=4)      # waits for the one slot
    eng.run()
    st = eng.stats
    first = st["first_use_s"]
    assert 0.0 < first <= st["prefill_dispatch_s"] + st["decode_dispatch_s"]
    assert 0.0 < st["admission_wait_max_s"] <= st["admission_wait_s"]
    # the second request waited at least as long as the first one ran
    assert st["admission_wait_max_s"] >= eng.recent_steps()[0]["wall_s"]
    # the same bucket and chunk lengths again: nothing is first use now
    eng.submit(_prompt(7), max_new=4)
    eng.run()
    assert eng.stats["first_use_s"] == first
    firsts = [r["iteration"] for r in eng.recent_steps() if r["first_use"]]
    assert firsts and firsts[0] == 1
    assert not eng.recent_steps()[-1]["first_use"]


def test_speculative_dispatches_count_as_chunks(params):
    eng = _engine(params, spec_draft=2)
    eng.submit(_prompt(8), max_new=9)
    n = _drive(eng)
    st = eng.stats
    assert st["spec_iterations"] > 0
    assert st["chunks"] == n
    assert st["decode_dispatch_s"] > 0.0 and st["decode_wait_s"] > 0.0
    windows = {r["chunk"] for r in eng.recent_steps()}
    assert 3 in windows     # a verify window of spec_draft + 1 tokens


def test_tp_engine_inherits_the_counters(params):
    if len(jax.devices()) < 4:
        pytest.skip("needs virtual multi-device CPU")
    mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
    eng = TPLMEngine(params, H, MAXLEN, mesh, n_slots=2, chunk=4)
    eng.submit(_prompt(6), max_new=6)
    eng.submit(_prompt(9), max_new=5)
    n = _drive(eng)
    st = eng.stats
    assert st["iterations"] == n and st["chunks"] == n
    assert st["prefills"] == 2
    assert all(st[f"{p}_s"] > 0.0 for p in STEP_PHASES)
    assert sum(st[f"{p}_s"] for p in STEP_CHILDREN) <= st["step_s"]
    assert [r["admitted"] for r in eng.recent_steps()][0] == [[0, 0], [1, 1]]


# --------------------------------------------------------------------------- #
# the primitive
# --------------------------------------------------------------------------- #

def test_phase_adds_to_its_key_and_keeps_its_stamps():
    stats = {"thing_s": 1.0}
    before = time.monotonic_ns()
    with tracing.phase(stats, "serving.thing") as ph:
        pass
    after = time.monotonic_ns()
    assert before <= ph.start_ns <= ph.end_ns <= after
    assert stats["thing_s"] == pytest.approx(1.0 + ph.seconds)
    assert ph.seconds == (ph.end_ns - ph.start_ns) / 1e9


def test_phase_records_spans_only_under_a_recorded_parent(tracing_on):
    stats = {"a_s": 0.0, "b_s": 0.0}
    tracing_on.sample_every = 2      # admits the 1st, 3rd, .. root
    kept = []
    for _ in range(4):
        with tracing.phase(stats, "serving.a") as root:
            with tracing.phase(stats, "serving.b", parent=root):
                pass
        kept.append(root._span is not None)
    assert kept == [True, False, True, False]
    done = [t for t in tracing_on.summaries() if t["completed"]]
    assert len(done) == 2 and all(t["spans"] == 2 for t in done)
    assert stats["a_s"] >= stats["b_s"] > 0.0


def test_tracing_module_needs_no_jax():
    """obs/tracing.py stays importable, and ``phase`` usable, where jax
    is not: loaded by path, with the import of jax made to fail."""
    code = (
        "import sys, importlib.util\n"
        "sys.modules['jax'] = None\n"
        "spec = importlib.util.spec_from_file_location('t', sys.argv[1])\n"
        "t = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(t)\n"
        "st = {'x_s': 0.0}\n"
        "with t.phase(st, 'serving.x') as ph:\n"
        "    pass\n"
        "assert st['x_s'] == ph.seconds >= 0.0 and t._ANNOTATION is False\n"
        "print('ok')\n")
    p = subprocess.run(
        [sys.executable, "-c", code,
         os.path.join(ROOT, "nnstreamer_tpu", "obs", "tracing.py")],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"


# --------------------------------------------------------------------------- #
# spans: the store and the profiler
# --------------------------------------------------------------------------- #

def test_tracing_off_records_no_span_and_no_annotation(params):
    assert not tracing.enabled()
    tracing.store().reset()
    assert TraceAnnotation.is_enabled() is False
    eng = _engine(params)
    eng.submit(_prompt(5), max_new=4)
    eng.run()
    assert tracing.store().summaries() == []
    assert eng.stats["step_s"] > 0.0


@pytest.mark.parametrize("kind", KINDS)
def test_one_iteration_is_one_trace_with_the_phases_as_children(
        params, tracing_on, kind):
    eng = _engine(params, kind)
    eng.submit(_prompt(5), max_new=6)
    n = _drive(eng)
    steps = [t for t in tracing_on.summaries()
             if t["root"] == "serving.step"]
    assert len(steps) == n and all(t["completed"] for t in steps)
    trees = [tracing_on.tree(t["trace_id"])["tree"] for t in steps]
    assert all(len(roots) == 1 for roots in trees)
    by_iteration = {roots[0]["attrs"]["iteration"]: roots[0]
                    for roots in trees}
    assert sorted(by_iteration) == list(range(1, n + 1))
    first = by_iteration[1]
    read = ["serving.decode_wait", "serving.retire"]
    # through the lane the engine runs a chunk ahead: an idle engine
    # dispatches the chunk that carries the prompt's window and the
    # decode chunk behind it, then reads the first
    assert [c["name"] for c in first["children"]] == ["serving.admit"] \
        + ["serving.decode_dispatch"] * (2 if kind == "lane" else 1) + read
    admit = first["children"][0]
    assert [c["name"] for c in admit["children"]] == [
        f"serving.{p}" for p in _admit_children(kind)]
    # a later iteration admits nothing: no admit span at all
    assert [c["name"] for c in by_iteration[2]["children"]] == [
        "serving.decode_dispatch"] + read
    # the last chunk has none behind it: the iteration only reads
    assert [c["name"] for c in by_iteration[n]["children"]] == (
        read if kind == "lane" else ["serving.decode_dispatch"] + read)
    # the request's own tree is a trace apart, as before
    assert sum(t["root"] == "serving.request"
               for t in tracing_on.summaries()) == 1


@pytest.mark.parametrize("kind", KINDS)
def test_profiler_session_holds_the_spans_nested(params, tmp_path, kind):
    eng = _engine(params, kind)
    eng.submit(_prompt(5), max_new=4)
    eng.run()                                   # compile outside the trace
    eng.submit(_prompt(6), max_new=6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert TraceAnnotation.is_enabled()
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert TraceAnnotation.is_enabled() is False
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serving.")]
    names = {s[0] for s in spans}
    assert names == {f"serving.{p}" for p in _phases(kind)}
    steps = [s for s in spans if s[0] == "serving.step"]
    waits = [s for s in spans if s[0] == "serving.decode_wait"]
    # a wait a step, whatever the order of dispatch and read
    assert steps and len(waits) == len(steps)
    for _, a, b in waits:
        assert sum(1 for _, s0, s1 in steps if s0 <= a and b <= s1) == 1


# --------------------------------------------------------------------------- #
# the records and the stall
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", KINDS)
def test_records_say_what_each_iteration_did(params, kind):
    eng = _engine(params, kind)
    r0 = eng.submit(_prompt(5), max_new=3)
    r1 = eng.submit(_prompt(9), max_new=12)
    r2 = eng.submit(_prompt(4), max_new=3)      # waits for a slot
    n = _drive(eng)
    recs = eng.recent_steps()
    assert [r["iteration"] for r in recs] == list(range(1, n + 1))
    assert recs[0]["admitted"] == [[r0, 0], [r1, 1]]
    # the two admitted decode from the first chunk on, or (their prompts
    # in the lane, one step each) from the second iteration on
    assert recs[0]["queued"] == 1
    if kind == "lane":
        # a record says what the chunk READ in its iteration did: two
        # lane steps, then (dispatched before those were read) the chunk
        # of four behind the first tokens
        keys = ("active", "chunk", "lane_steps", "lane_rows", "lane_tokens")
        assert [recs[0][k] for k in keys] == [0, 2, 2, 2 * LANE_ROWS, 5 + 9]
        assert [recs[1][k] for k in keys] == [2, 4, 0, 0, 0]
        # the third request takes the first slot that opens (iteration
        # 3); its prompt's lane step is queued behind the chunk that was
        # ahead and read an iteration later, while the second decodes
        assert recs[2]["admitted"] == [[r2, 0]] and recs[2]["lane_steps"] == 0
        assert recs[3]["active"] == 1 and recs[3]["lane_steps"] == 1
    else:
        assert recs[0]["active"] == 2 and recs[0]["lane_steps"] == 0
    later = [r["admitted"] for r in recs[1:] if r["admitted"]]
    assert later == [[[r2, 0]]]
    for r in recs:
        assert r["chunk"] - r["lane_steps"] in (0, 1, 2, 4)
        assert r["wall_s"] >= 0.0 and r["cpu_s"] >= 0.0
        assert len(r["gc"]) == 3 and all(g >= 0 for g in r["gc"])
        assert set(r["phases"]) == set(STEP_PHASES)
        assert r["start_ns"] > 0
    assert [a["start_ns"] < b["start_ns"] for a, b in zip(recs, recs[1:])] \
        == [True] * (n - 1)
    # copies: a caller cannot edit the engine's records
    recs[0]["admitted"].append("x")
    recs[0]["phases"]["step"] = -1.0
    assert eng.recent_steps()[0]["admitted"] == [[r0, 0], [r1, 1]]
    assert eng.recent_steps()[0]["phases"]["step"] >= 0.0


def test_ring_is_bounded_and_the_slowest_outlive_it(params, monkeypatch):
    monkeypatch.setattr(LMEngine, "STEP_RING", 6)
    monkeypatch.setattr(LMEngine, "STEP_KEEP_SLOWEST", 3)
    eng = _engine(params)
    eng.submit(_prompt(5), max_new=40)
    n = _drive(eng)
    assert n > 9
    recent = eng.recent_steps()
    assert [r["iteration"] for r in recent] == list(range(n - 5, n + 1))
    slow = eng.slowest_steps()
    assert len(slow) == 3
    assert [r["wall_s"] for r in slow] == sorted(
        (r["wall_s"] for r in slow), reverse=True)
    assert not any(r["first_use"] for r in slow)
    # nothing faster than the kept ones was dropped for them: the kept
    # floor is at least the wall of every recent ordinary iteration
    # that is not itself kept
    kept = {r["iteration"] for r in slow}
    floor = min(r["wall_s"] for r in slow)
    assert all(r["wall_s"] <= floor for r in recent
               if r["iteration"] not in kept and not r["first_use"])


@pytest.mark.parametrize("kind", KINDS)
def test_a_stalled_dispatch_is_named_and_warned_once(
        params, monkeypatch, caplog, events_on, kind):
    eng = _engine(params, kind)
    eng.submit(_prompt(5), max_new=6)
    eng.run()                       # every program this test uses, warm
    run_chunk = LMEngine._run_chunk
    calls = []

    def stalls_once(self, n):
        calls.append(n)
        if len(calls) == 2:
            time.sleep(LMEngine.STEP_STALL_S + 0.05)
        return run_chunk(self, n)

    monkeypatch.setattr(LMEngine, "_run_chunk", stalls_once)
    eng.submit(_prompt(6), max_new=13)
    start = eng.stats["iterations"]
    with caplog.at_level(logging.WARNING, logger="nns_tpu.serving"):
        eng.run()
    assert len(calls) >= 3
    worst = eng.slowest_steps()[0]
    # the second dispatch: the next iteration's, or the chunk behind the
    # first token in the same one
    assert worst["iteration"] == start + (1 if kind == "lane" else 2)
    assert worst["wall_s"] > LMEngine.STEP_STALL_S
    assert max(worst["phases"], key=lambda p: worst["phases"][p]
               if p != "step" else -1.0) == "decode_dispatch"
    # the thread slept: wall far above its CPU time
    assert worst["cpu_s"] < worst["wall_s"] / 2
    warned = [r for r in caplog.records if "stood still" in r.getMessage()]
    assert len(warned) == 1
    assert f"iteration {worst['iteration']}" in warned[0].getMessage()
    assert "decode_dispatch" in warned[0].getMessage()
    stalls = [e for e in events_on.snapshot()
              if e["type"] == "serving.step_stall"]
    assert len(stalls) == 1
    assert stalls[0]["attrs"]["step"]["iteration"] == worst["iteration"]
    assert stalls[0]["severity"] == "warning"


def test_a_slow_first_use_iteration_is_no_stall(params, monkeypatch, caplog):
    run_chunk = LMEngine._run_chunk
    calls = []

    def slow_first(self, n):
        calls.append(n)
        if len(calls) == 1:
            time.sleep(LMEngine.STEP_STALL_S + 0.05)
        return run_chunk(self, n)

    monkeypatch.setattr(LMEngine, "_run_chunk", slow_first)
    eng = _engine(params)
    eng.submit(_prompt(5), max_new=6)
    with caplog.at_level(logging.WARNING, logger="nns_tpu.serving"):
        eng.run()
    first = eng.recent_steps()[0]
    assert first["first_use"] and first["wall_s"] > LMEngine.STEP_STALL_S
    assert eng.stats["first_use_s"] > LMEngine.STEP_STALL_S
    assert all(r["iteration"] != 1 for r in eng.slowest_steps())
    assert not [r for r in caplog.records if "stood still" in r.getMessage()]


@pytest.mark.parametrize("kind", KINDS)
def test_hooks_take_the_phases_stamps(params, monkeypatch, kind):
    """The profile hook's decode interval is the dispatch phase's start
    to the wait phase's end: the same stamps, no clock of its own. A
    prefill through the lane is inside the decode intervals and has none
    of its own."""
    got = []

    class Hook:
        def record_engine(self, engine, phase, t0_ns, t1_ns, **kw):
            got.append((phase, t0_ns, t1_ns))

    monkeypatch.setattr(obs_profile, "ENGINE_HOOK", Hook())
    eng = _engine(params, kind)
    eng.submit(_prompt(5), max_new=6)
    eng.run()
    recs = eng.recent_steps()
    decodes = [g for g in got if g[0] == "decode"]
    assert len(decodes) == eng.stats["chunks"] == len(recs)
    for (_, t0, t1), rec in zip(decodes, recs):
        assert recs[0]["start_ns"] <= t0 <= t1 \
            <= rec["start_ns"] + int(rec["wall_s"] * 1e9) + 1
    if kind == "lane":
        # run ahead: a chunk's interval begins where the wait for the
        # one before it ended, and ends with its own wait
        assert [d[1] for d in decodes[1:]] == [d[2] for d in decodes[:-1]]
        for (_, t0, t1), rec in list(zip(decodes, recs))[1:]:
            assert (t1 - t0) / 1e9 >= rec["phases"]["decode_wait"] - 1e-9
        assert {g[0] for g in got} == {"decode"}
        return
    for (_, t0, t1), rec in zip(decodes, recs):
        ph = rec["phases"]
        assert rec["start_ns"] <= t0
        assert (t1 - t0) / 1e9 >= ph["decode_dispatch"] + ph["decode_wait"] \
            - 1e-9
    (prefill,) = [g for g in got if g[0] == "prefill"]
    ph = recs[0]["phases"]
    assert (prefill[2] - prefill[1]) / 1e9 >= ph["prefill_dispatch"] \
        + ph["slot_insert"] + ph["first_token_wait"] - 1e-9
    assert (prefill[2] - prefill[1]) / 1e9 <= ph["admit"]


# --------------------------------------------------------------------------- #
# read-only views
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", KINDS)
def test_progress_and_slot_of(params, kind):
    eng = _engine(params, kind, n_slots=1)
    a = eng.submit(_prompt(5), max_new=9)
    b = eng.submit(_prompt(6), max_new=3)
    assert eng.progress(a) == [] and eng.slot_of(a) is None   # queued
    assert eng.progress(99) is None and eng.slot_of(99) is None
    eng.step_iteration()
    assert eng.slot_of(a) == 0 and eng.slot_of(b) is None
    if kind == "lane":
        # the lane's chunk is read and the chunk behind it is in flight:
        # the first token waits for that chunk's tokens
        assert eng.progress(a) == []
        eng.step_iteration()
    so_far = eng.progress(a)
    # the first token, and with it the chunk behind it
    first = 1 + 4
    assert len(so_far) == first
    so_far.append(-1)                          # a copy
    assert len(eng.progress(a)) == first
    assert eng.progress(b) == []
    eng.run()
    assert eng.progress(a) == eng.results[a] and len(eng.progress(a)) == 9
    assert eng.progress(b) == eng.results[b]
    assert eng.slot_of(a) is None and eng.slot_of(b) is None
    took = {rid: slot for r in eng.recent_steps() for rid, slot in r["admitted"]}
    assert took == {a: 0, b: 0}
