"""``correct`` at a toy size on the CPU: sound runs pass, the control (the
reference in the nearest lower precision, put in the program's place)
fails, and a run whose timed path alters a token where it is produced
comes out not correct. The float control is ``high``, three bfloat16
passes: at this size it flips no token, and fails by the keys and values
alone; the int4 control of the w8a8 tree fails by both numbers."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tools import seeds

TINY = "benchmark/tests/data/BENCHMARK.tiny.json"


@pytest.fixture(scope="module", autouse=True)
def _cache():
    harness.place_compile_cache()


@pytest.mark.parametrize("name", ["tiny_selftest", "tiny_selftest_w8a8"])
def test_program_passes_and_control_fails(name):
    cell = harness.load_cell(name, TINY)
    for seed in (3, 4, 5):
        row = seeds.read_seed(cell, seed, 3.0, with_control=True)
        assert row["failed"] == 0
        assert row["correct"], row
        assert row["program_gap_max"] <= row["limit"]
        assert row["program_kv_gap_max"] <= row["kv_limit"]
        assert len(row["kv_rows"]) == 2
        assert not row["control_correct"], row
        assert row["control_kv_gap_max"] >= 3 * max(
            row["program_kv_gap_max"], row["kv_limit"])


@pytest.mark.parametrize("name", ["tiny_selftest", "tiny_selftest_w8a8"])
def test_altered_token_is_not_correct(name, monkeypatch):
    from nnstreamer_tpu.serving.lm_engine import LMEngine

    cell = harness.load_cell(name, TINY)
    sound = harness.run_cell(cell, 6, 2.0, False, time.time(),
                             need_tpu=False)
    assert sound["correct"] is True and sound["failed"] == 0

    run_chunk = LMEngine._run_chunk

    def altered(self, n):
        outs = np.array(run_chunk(self, n))
        outs[:, -1] = (outs[:, -1] + 1) % cell.config["vocab_size"]
        return outs

    monkeypatch.setattr(LMEngine, "_run_chunk", altered)
    broken = harness.run_cell(cell, 6, 2.0, False, time.time(),
                              need_tpu=False)
    assert broken["correct"] is False
    for number in ("gap_max", "kv_gap_max"):
        assert broken["compared"][number]["value"] \
            > broken["compared"][number]["limit"]


def test_result_line_has_the_contracts_keys_with_compared_last():
    cell = harness.load_cell("tiny_selftest", TINY)
    out = harness.run_cell(cell, 2**31 + 17, 2.0, False, time.time(),
                           need_tpu=False)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "setup_s"}
    assert out["harness"]["compiles_in_window"] == 0
