"""The readers of the engine's wait on the device (``readback_ms_per_chunk``
and the witness's ``wait_*_ms_per_chunk``) on hand-made ``LMEngine.stats``:
their arithmetic, None where the program has no such counter (the parent
of the change that brought them), None on a window without a chunk; and
on a toy window of the engine, where each reads a number."""

from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.layers import (readback_ms_per_chunk,
                              wait_delivered_late_ms_per_chunk,
                              wait_held_ms_per_chunk,
                              wait_unreturned_ms_per_chunk)

TINY = "benchmark/tests/data/BENCHMARK.tiny.json"

#: what the parent's engine has: the wait, not its parts
OLD = {"decode_steps": 80, "chunks": 11, "decode_wait_s": 1.5}
START = {**OLD, "device_wait_s": 1.25, "readback_s": 0.0625,
         "wait_unready_s": 1.0, "wait_unreturned_s": 0.125,
         "wait_held_s": 0.0625, "wait_delivered_late_s": 0.0}
END = {**START, "decode_steps": 880, "chunks": 111, "decode_wait_s": 15.5,
       "device_wait_s": 14.75, "readback_s": 0.1625,
       "wait_unready_s": 11.25, "wait_unreturned_s": 0.625,
       "wait_held_s": 2.5625, "wait_delivered_late_s": 0.125}

READERS = [
    # 0.1 s of readback over 100 chunks
    (readback_ms_per_chunk, 0.1 / 100 * 1e3),
    (wait_unreturned_ms_per_chunk, 0.5 / 100 * 1e3),
    (wait_held_ms_per_chunk, 2.5 / 100 * 1e3),
    (wait_delivered_late_ms_per_chunk, 0.125 / 100 * 1e3),
]


def ctx_of(start, end):
    return SimpleNamespace(
        window=SimpleNamespace(stats_start=start, stats_end=end))


@pytest.mark.parametrize("reader,want", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1]
                              for r, _ in READERS])
def test_wait_readers(reader, want):
    assert reader.read(ctx_of(START, END)) == pytest.approx(want)
    # the parent's engine has no such counter
    assert reader.read(ctx_of(OLD, OLD)) is None
    assert reader.read(ctx_of(OLD, END)) is None
    # a window without a chunk
    assert reader.read(ctx_of(START, START)) is None


def test_toy_window_reads_every_part():
    cell = harness.load_cell("tiny_selftest", TINY)
    adapter, sched = harness.set_up(cell, 2**31 + 41, 1.5)
    window = harness.run_window(adapter, sched, 1.5)
    ctx = harness.Context(cell, window, 0.0, {}, adapter)
    got = {r.__name__.rsplit(".", 1)[1]: r.read(ctx) for r, _ in READERS}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    d = {k: window.stats_end[k] - window.stats_start[k]
         for k in ("decode_wait_s", "device_wait_s", "readback_s",
                   "wait_unready_s", "wait_unreturned_s", "wait_held_s",
                   "wait_delivered_late_s")}
    # the two phases lie inside the wait, the four parts inside the first
    assert d["device_wait_s"] + d["readback_s"] <= d["decode_wait_s"] + 1e-9
    assert sum(d[k] for k in d if k.startswith("wait_")) \
        <= d["device_wait_s"] + 1e-6
