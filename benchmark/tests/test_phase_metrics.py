"""The readers of ``LMEngine``'s step-phase counters on hand-made ``stats``
(their arithmetic, None on an engine without the counters, None on a base
of 0), and the span-stack walk of ``tools/phases.py`` on a hand-made event
list."""

from types import SimpleNamespace

import pytest

from benchmark.layers import (admission_wait_ms, admit_stall_ms,
                              decode_wait_ms_per_step, engine_first_use_s,
                              step_host_ms_per_chunk, step_longest_ms)
from benchmark.tools import phases

#: what the parent's engine has
OLD = {"prefills": 0, "decode_steps": 0, "slot_steps": 0,
       "wasted_slot_steps": 0, "tokens_out": 0, "wall_s": 0.0}

START = {**OLD, "prefills": 10, "decode_steps": 80, "iterations": 12,
         "chunks": 11, "step_s": 3.0, "admit_s": 1.0, "decode_wait_s": 1.5,
         "first_token_wait_s": 0.25, "admission_wait_s": 0.5,
         "first_use_s": 2.75}
END = {**START, "prefills": 14, "decode_steps": 880, "iterations": 113,
       "chunks": 111, "step_s": 19.0, "admit_s": 1.25,
       "decode_wait_s": 15.5, "first_token_wait_s": 0.5,
       "admission_wait_s": 0.75}


class Engine:
    def __init__(self, slow, recent):
        self._slow, self._recent = slow, recent

    def slowest_steps(self):
        return list(self._slow)

    def recent_steps(self):
        return list(self._recent)


def ctx_of(start, end, engine=None):
    return SimpleNamespace(
        window=SimpleNamespace(stats_start=start, stats_end=end),
        adapter=SimpleNamespace(engine=engine))


def step(iteration, wall_s, first_use=False):
    return {"iteration": iteration, "wall_s": wall_s, "first_use": first_use}


READERS = [
    # (16 - 14 - 0.25) s of a step not blocked on the device, 100 chunks
    (step_host_ms_per_chunk, (16.0 - 14.0 - 0.25) / 100 * 1e3),
    # 14 s blocked on the chunks' readback, 800 decode steps
    (decode_wait_ms_per_step, 14.0 / 800 * 1e3),
    # 0.25 s in serving.admit, 4 admissions
    (admit_stall_ms, 0.25 / 4 * 1e3),
    (admission_wait_ms, 0.25 / 4 * 1e3),
]


@pytest.mark.parametrize("reader,want", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1]
                              for r, _ in READERS])
def test_counter_readers(reader, want):
    assert reader.read(ctx_of(START, END)) == pytest.approx(want)
    # the parent's engine: no such counters, nothing to report
    assert reader.read(ctx_of(OLD, OLD)) is None
    assert reader.read(ctx_of(OLD, END)) is None
    # a window in which the base did not move
    assert reader.read(ctx_of(START, START)) is None


def test_engine_first_use_reads_what_set_up_spent():
    assert engine_first_use_s.read(ctx_of(START, END)) == 2.75
    assert engine_first_use_s.read(ctx_of(OLD, OLD)) is None


def test_step_longest_takes_the_windows_ordinals_only():
    slow = [step(5, 9.0),           # before the window (set-up)
            step(40, 0.5),
            step(200, 7.0)]         # after its close (the drain)
    recent = [step(12, 8.0),        # the start's own ordinal: before it
              step(13, 0.25), step(40, 0.5), step(77, 0.75, first_use=True),
              step(113, 0.375), step(114, 6.0)]
    eng = Engine(slow, recent)
    assert step_longest_ms.read(ctx_of(START, END, eng)) == 500.0
    # the stalled iteration, kept among the slowest after the ring let go
    eng = Engine(slow + [step(60, 2.5)], recent)
    assert step_longest_ms.read(ctx_of(START, END, eng)) == 2500.0
    # no record in the window, the parent's stats, the parent's engine
    assert step_longest_ms.read(ctx_of(START, END, Engine([], []))) is None
    assert step_longest_ms.read(ctx_of(OLD, OLD, eng)) is None
    assert step_longest_ms.read(ctx_of(START, END, object())) is None
    assert step_longest_ms.read(
        SimpleNamespace(window=SimpleNamespace(stats_start=START,
                                               stats_end=END),
                        adapter=None)) is None


# --------------------------------------------------------------------------- #
# tools/phases.py
# --------------------------------------------------------------------------- #

#: two iterations as the profiler writes them, out of order on purpose;
#: [name, start_ns, dur_ns]
HOST = [
    ["bench.stamp", 1010.0, 20.0],
    ["serving.decode_wait", 420.0, 480.0],
    ["bench.step_iteration", 90.0, 910.0],
    ["serving.step", 100.0, 850.0],
    ["serving.admit", 110.0, 200.0],
    ["serving.admit_host", 120.0, 60.0],
    ["serving.prefill_dispatch", 180.0, 100.0],
    ["serving.decode_dispatch", 340.0, 60.0],
    ["serving.retire", 900.0, 40.0],
    ["bench.submit", 1050.0, 10.0],
    ["bench.step_iteration", 1100.0, 400.0],
    ["serving.step", 1110.0, 380.0],
    ["serving.decode_dispatch", 1120.0, 30.0],
    ["serving.decode_wait", 1150.0, 300.0],
]


def test_stack_walk_cuts_at_the_innermost_span():
    segs = phases.innermost_segments(HOST)
    # no overlap, in order, and only where some span is open
    for (a0, a1, _), (b0, _, _) in zip(segs, segs[1:]):
        assert a0 < a1 <= b0
    at = {t: name for a, b, name in segs for t in range(int(a), int(b), 10)}
    assert at[90] == "bench.step_iteration"     # before serving.step opens
    assert at[100] == "serving.step"
    assert at[110] == "serving.admit"           # admit's own time
    assert at[150] == "serving.admit_host"      # nested two deep
    assert at[200] == "serving.prefill_dispatch"    # its sibling
    assert at[290] == "serving.admit"           # after the children close
    assert at[320] == "serving.step"            # between admit and dispatch
    assert at[400] == "serving.step"            # between two phases
    assert at[500] == "serving.decode_wait"
    assert at[940] == "serving.step"
    assert at[960] == "bench.step_iteration"    # after serving.step closed
    assert 1000 not in at and 1040 not in at    # no span open: the loop
    assert at[1020] == "bench.stamp"
    assert at[1460] == "serving.step"
    assert sum(b - a for a, b, _ in segs) == 910 + 20 + 10 + 400


def test_idle_time_goes_to_the_innermost_phase_or_the_harness():
    events = {
        "host": HOST,
        "devices": {"/device:TPU:0": {
            "modules": [["jit__prefill_admit(1)", 200.0, 150.0],
                        # the program's event outlasts its last op by 5
                        ["jit__decode_chunk(2)", 400.0, 495.0],
                        ["jit__decode_chunk(2)", 1160.0, 280.0]],
            "ops": [["fusion.1", 200.0, 150.0],
                    ["while.3", 400.0, 490.0],      # a container: not busy
                    ["fusion.7", 400.0, 200.0],
                    ["fusion.8", 610.0, 280.0],
                    ["fusion.7", 1160.0, 280.0]]}}}
    out = phases.idle_by_phase(events)
    by = {k: v * 1e9 for k, v in out["by_phase"].items()}
    # idle: [90,200) [350,400) [600,610) [890,1160) [1440,1500)
    assert out["window_s"] * 1e9 == pytest.approx(1410.0)
    assert out["idle_s"] * 1e9 == pytest.approx(110 + 50 + 10 + 270 + 60)
    assert by["serving.admit_host"] == pytest.approx(60.0)     # 120-180
    assert by["serving.prefill_dispatch"] == pytest.approx(20.0)  # 180-200
    assert by["serving.admit"] == pytest.approx(10.0)          # 110-120
    # 350-400 lies in decode_dispatch (340-400); 1120-1150 and 10 ns of
    # the second step's wait come later
    assert by["serving.decode_dispatch"] == pytest.approx(50.0 + 30.0)
    # 600-610 inside the first wait, 890-900 at its end, 1150-1160 and
    # 1440-1450 in the second
    assert by["serving.decode_wait"] == pytest.approx(10 + 10 + 10 + 10)
    assert by["serving.retire"] == pytest.approx(40.0)
    # serving.step's own time: 100-110, 940-950, 1110-1120, 1450-1490
    assert by["serving.step"] == pytest.approx(10 + 10 + 10 + 40)
    # inside bench.step_iteration, outside serving.step: 90-100, 950-1000,
    # 1100-1110, 1490-1500
    assert by[phases.STEP_WRAP] == pytest.approx(10 + 50 + 10 + 10)
    # stamp 1010-1030, submit 1050-1060 and the loop around them
    assert by[phases.HARNESS] == pytest.approx(100.0)
    assert sum(by.values()) == pytest.approx(out["idle_s"] * 1e9)
    both = out["by_phase_and_program"]
    assert both["serving.admit_host | before jit__prefill_admit"] * 1e9 \
        == pytest.approx(60.0)
    # a gap is cut where the program ends as well: 600-610 and 890-895
    # lie inside the first chunk's event, 895-900 and 1150-1160 before
    # the second, 1440-1450 after the last
    assert both["serving.decode_wait | in jit__decode_chunk"] * 1e9 \
        == pytest.approx(15.0)
    assert both["serving.decode_wait | before jit__decode_chunk"] * 1e9 \
        == pytest.approx(15.0)
    assert both["serving.decode_wait | before end of trace"] * 1e9 \
        == pytest.approx(10.0)
    assert both[f"{phases.HARNESS} | before jit__decode_chunk"] * 1e9 \
        == pytest.approx(100.0)


def test_nesting_and_walls_of_the_hand_made_list():
    nest = phases.nesting(HOST)
    assert nest["serving.step"] == ["bench.step_iteration"]
    assert nest["serving.admit_host"] == ["serving.admit"]
    assert nest["serving.decode_wait"] == ["serving.step"]
    assert nest["bench.stamp"] == [""]
    walls = phases.span_walls(HOST)
    assert walls["serving.decode_wait"] == [2, pytest.approx(780e-9)]
    assert walls["bench.step_iteration"][0] == 2


def test_a_trace_without_the_engines_spans_is_all_harness():
    """The parent's engine writes no ``serving.*`` span: every idle
    instant inside the step is the wrapper's row, none is lost."""
    events = {
        "host": [e for e in HOST if e[0].startswith("bench.")],
        "devices": {"/device:TPU:0": {
            "modules": [["jit__decode_chunk(2)", 400.0, 490.0]],
            "ops": [["fusion.7", 400.0, 490.0]]}}}
    out = phases.idle_by_phase(events)
    by = {k: v * 1e9 for k, v in out["by_phase"].items()}
    assert set(by) == {phases.STEP_WRAP, phases.HARNESS}
    assert by[phases.STEP_WRAP] == pytest.approx(310 + 110 + 400)
    assert sum(by.values()) == pytest.approx(1410.0 - 490.0)
    assert phases.idle_by_phase({"host": [], "devices": {}})["idle_s"] == 0.0
