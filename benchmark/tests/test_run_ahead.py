"""``run_ahead_share`` on hand-made ``LMEngine.stats`` (its arithmetic, None
where the program has no such counter: the parent of the PR that brought
it) and on a toy window of the engine that runs a chunk ahead: the
adapter's prediction of admissions still holds there, so ``resident``
returns rows and the run is ``correct``."""

import time
from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.layers import run_ahead_share

TINY = "benchmark/tests/data/BENCHMARK.tiny.json"

OLD = {"decode_steps": 0, "chunks": 0}
START = {"decode_steps": 80, "chunks": 11, "chunks_ahead": 9}
END = {"decode_steps": 880, "chunks": 111, "chunks_ahead": 99}


def ctx_of(start, end):
    return SimpleNamespace(
        window=SimpleNamespace(stats_start=start, stats_end=end))


def test_share_is_chunks_ahead_over_chunks():
    assert run_ahead_share.read(ctx_of(START, END)) == pytest.approx(90.0)
    # the parent's engine has no such counter
    assert run_ahead_share.read(ctx_of(OLD, OLD)) is None
    assert run_ahead_share.read(ctx_of(OLD, END)) is None
    # a window without a chunk
    assert run_ahead_share.read(ctx_of(START, START)) is None


@pytest.fixture(scope="module")
def driven():
    cell = harness.load_cell("tiny_selftest", TINY)
    adapter, sched = harness.set_up(cell, 2**31 + 31, 1.5)
    window = harness.run_window(adapter, sched, 1.5)
    return cell, adapter, window


def test_toy_window_runs_ahead_and_the_rule_holds(driven):
    cell, adapter, window = driven
    eng = adapter.engine
    share = run_ahead_share.read(
        harness.Context(cell, window, 0.0, {}, adapter))
    assert 0.0 < share <= 100.0
    # the harness's drain left a quiet engine: nothing in flight
    assert eng._flight is None and eng.pending() == 0
    assert all(r.finished for r in window.requests)
    assert adapter._rule_held
    most = int(cell.params["check"]["kv_slots"])
    assert len(adapter.resident(most)) == most
    # a first token is stamped with the chunk behind it
    for r in window.requests:
        assert r.max_new == 1 or r.token_s[0] == r.token_s[1]


def test_toy_run_is_correct():
    cell = harness.load_cell("tiny_selftest", TINY)
    out = harness.run_cell(cell, 2**31 + 31, 2.0, False, time.time(),
                           need_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["harness"]["compiles_in_window"] == 0
    assert out["compared"]["kv_shortfall"]["value"] == 0.0
