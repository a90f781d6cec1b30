"""``correct`` for the ``qwen3_next`` family at a toy size on the CPU:
sound runs pass, the control (the reference with its contractions at
``high``, put in the program's place) fails, and a run whose timed path
alters a token where it is produced comes out not correct. At this size
the control flips no token and fails by the full layers' K and V rows
alone (``kv_gap_max``): three bfloat16 passes leave a row some 2.5e-4 off,
the program's float32 rounding some 6e-6 (the chunked lane form and the
recurrence carry rounding from row to row)."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tools import seeds

TINY = "benchmark/tests/data/BENCHMARK.tiny-qwen3next.json"
CELL = "tiny_qwen3next_selftest"


@pytest.fixture(scope="module", autouse=True)
def _cache():
    harness.place_compile_cache()


def test_program_passes_and_control_fails():
    cell = harness.load_cell(CELL, TINY)
    for seed in (3, 4):
        row = seeds.read_seed(cell, seed, 2.0, with_control=seed == 3)
        assert row["failed"] == 0
        assert row["correct"], row
        assert row["program_gap_max"] <= row["limit"]
        assert row["program_kv_gap_max"] <= row["kv_limit"]
        assert len(row["kv_rows"]) == 2
        if seed == 3:
            assert not row["control_correct"], row
            assert row["control_kv_gap_max"] >= 3 * max(
                row["program_kv_gap_max"], row["kv_limit"])


def test_altered_token_is_not_correct(monkeypatch):
    from nnstreamer_tpu.serving.lm_engine import LMEngine

    cell = harness.load_cell(CELL, TINY)
    sound = harness.run_cell(cell, 6, 2.0, False, time.time(),
                             need_tpu=False)
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert sound["harness"]["compiles_in_window"] == 0

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


def test_a_routing_tie_is_resolved_in_the_reference_alone(capsys):
    """With ``route_tie`` wide enough that rows are tied (the 4th and 5th
    of 16 probabilities), the reference tries the second pick at each and
    keeps the one the program served: the run stays correct under the same
    limits, and the count of tied token-layers is printed."""
    cell = harness.load_cell(CELL, TINY)
    cell.config = {**cell.config, "route_tie": 2e-3}
    cell.params = {**cell.params,
                   "check": {**cell.params["check"], "requests": 3,
                             "kv_slots": 1}}
    out = harness.run_cell(cell, 8, 1.0, False, time.time(), need_tpu=False)
    assert out["correct"] is True, out["compared"]
    said = capsys.readouterr().err
    tied = int(said.split("compared route_ties:")[1].split()[0])
    assert tied > 0


def _tied_reference(route_tie=2e-3):
    import json

    from benchmark.reference import qwen3_next as ref_mod

    with open("benchmark/configs/tiny-qwen3next-selftest.json") as f:
        cfg = json.load(f)
    return ref_mod, ref_mod.Reference({**cfg, "route_tie": route_tie}, 5)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def test_a_second_pick_is_found_from_a_later_full_layers_rows():
    """Rows kept by the same arithmetic with one second pick in layer 0,
    three layers before the full layer that stores them: the tie search
    finds that pick from the token's own rows there and the rows then
    agree to the bit; the gaps of the served tokens alone cannot see it."""
    import jax.numpy as jnp

    ref_mod, ref = _tied_reference()
    seq = _tokens(48, 1)
    ids, rows, tp = ref._pad(seq)
    h0 = ref.embed[jnp.asarray(ids)]
    ties = ref._forward(h0, 0, {}, rows, None)[4]
    assert ties[0], "no tie in layer 0 at this size"
    second = np.zeros((tp,), bool)
    second[ties[0][-1]] = True
    kept = ref._forward(h0, 0, {0: second}, rows, None)[2]
    store = tuple(np.concatenate([np.asarray(z)[:rows].transpose(1, 0, 2)
                                  for z in mine]) for mine in zip(
        *[k for k in kept if k is not None]))
    served = np.append(seq[8:], 0)
    kv_gap = ref.score(seq[:8], served, kv=store)[2]
    assert ref.took_second >= 1
    assert kv_gap == 0.0


def test_a_tie_no_gap_lies_behind_runs_no_second_forward(monkeypatch):
    """Served tokens that are this model's best everywhere: every tie is
    decided without a second forward (no pick lowers a sum of zeros). A
    served token below the best at the end sends the ties before it
    through a second forward."""
    ref_mod, ref = _tied_reference()
    prompt = _tokens(40, 2)
    seq = list(prompt)
    for _ in range(8):
        seq.append(int(np.argmax(ref.logits(seq)[-1])))
    served = np.asarray(seq[40:], np.int32)
    calls = []
    forward = ref._forward
    monkeypatch.setattr(ref, "_forward",
                        lambda *a, **k: calls.append(a[1]) or forward(*a,
                                                                      **k))
    gap = ref.score(prompt, served)[0]
    assert np.all(gap == 0.0) and ref.tied > 0
    assert calls == [0]
    calls.clear()
    served[-1] = (served[-1] + 1) % 256
    gap = ref.score(prompt, served)[0]
    assert gap[-1] > 0.0
    assert len(calls) > 1
