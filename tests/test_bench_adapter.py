"""The benchmark's adapter over a toy ``LMEngine`` that admits through the
prompt lane: what ``benchmark/adapters/lm_engine.py`` predicts of the
engine (free slots take the queue's head in ascending order, a request
holds its slot from that iteration on), what ``benchmark/work.py`` reads
from an iteration (the first token from the prefill, the rest from decode
steps) and what ``warm`` has to have run. CPU, the benchmark's own toy
cell; nothing here is a time."""

import numpy as np
import pytest

from benchmark import harness, work

TINY = "benchmark/tests/data/BENCHMARK.tiny.json"


@pytest.fixture(scope="module")
def driven():
    """One warmed window of the toy cell, driven to its end."""
    cell = harness.load_cell("tiny_selftest", TINY)
    adapter, sched = harness.set_up(cell, 2**31 + 29, 1.5)
    warmed = adapter.stats()
    window = harness.run_window(adapter, sched, 1.5)
    return cell, adapter, sched, warmed, window


def test_more_requests_than_slots_and_the_rule_held(driven):
    cell, adapter, sched, _, window = driven
    eng = adapter.engine
    assert eng._lane
    assert len(sched) > 2 * eng.n_slots
    assert all(r.finished for r in window.requests)
    assert adapter._rule_held
    took = [slot for rec in eng.recent_steps()
            for _, slot, _ in rec["admitted"]]
    assert len(set(took)) > 1       # more than one slot was taken again


def test_resident_returns_the_rows_of_finished_requests(driven):
    cell, adapter, _, _, window = driven
    most = int(cell.params["check"]["kv_slots"])
    rows = adapter.resident(most)
    assert len(rows) == most
    lh = int(cell.config["n_layer"]) * int(cell.config["n_head"])
    for req, k, v in rows:
        n = int(req.prompt.size) + len(req.out) - 1
        assert req.done and k.shape == v.shape == (lh, n, 64 // 4)
        assert np.abs(k).max() > 0 and np.isfinite(k).all()
    state = harness.resident_state(cell, window, adapter)
    assert len(state) == most


def test_tally_splits_a_first_iteration_as_the_engine_counts(driven):
    """An iteration in which a request goes from no token to n is read
    as one token from the prefill and n - 1 from decode steps (those of
    the same chunk after the prompt's last window); summed over the run
    the split is the engine's own count of kept slot-steps."""
    cell, adapter, _, warmed, window = driven
    ctx = harness.Context(cell, window, 0.0, {}, adapter)
    w = work.tally(ctx, window.iterations)
    end = adapter.stats()
    diff = {k: end[k] - warmed[k] for k in end}
    assert len(w.prefills) == diff["prefills"] == len(window.requests)
    assert sorted(w.prefills) == sorted(
        int(r.prompt.size) for r in window.requests)
    assert w.decode_steps == diff["decode_steps"]
    slots = adapter.engine.n_slots
    assert w.kept_slot_steps \
        == slots * diff["decode_steps"] - diff["wasted_slot_steps"] \
        == diff["tokens_out"] - diff["prefills"]
    assert diff["lane_tokens"] == sum(w.prefills)
    firsts = [(before, after) for it in window.iterations
              for _, before, after in it.progress if before == 0]
    assert len(firsts) == len(window.requests)
    assert all(after >= 1 for _, after in firsts)


def test_after_warm_nothing_is_used_for_the_first_time(driven):
    _, adapter, _, warmed, window = driven
    eng = adapter.engine
    assert adapter.stats()["first_use_s"] == warmed["first_use_s"]
    assert window.stats_start["first_use_s"] == warmed["first_use_s"]
    late = [r for r in eng.recent_steps()
            if r["iteration"] > warmed["iterations"]]
    assert late and not any(r["first_use"] for r in late)
    # the plain chunk at every step count, and the ONE lane program: its
    # step count is data, so the lone requests of ``warm`` have run what
    # any number of waiting prompts of any length will
    assert eng._seen_programs == {("lane", 8)} | {
        ("chunk", n) for n in (1, 2, 4, 8)}
