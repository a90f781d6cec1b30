"""``correct`` for the ``glm4_moe_lite`` family at a toy size on the CPU:
sound runs pass, the control (the reference with its contractions at
``high``, put in the program's place) fails, and a run whose timed path
alters a token where it is produced comes out not correct. At this size
the control flips no token and fails by the latent rows alone
(``kv_gap_max``): three bfloat16 passes leave a row some 3e-5 off, the
program's float32 rounding 1e-6."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tools import seeds

TINY = "benchmark/tests/data/BENCHMARK.tiny-glm47.json"
CELL = "tiny_glm47_selftest"


@pytest.fixture(scope="module", autouse=True)
def _cache():
    harness.place_compile_cache()


def test_program_passes_and_control_fails():
    cell = harness.load_cell(CELL, TINY)
    for seed in (3, 4):
        row = seeds.read_seed(cell, seed, 3.0, with_control=True)
        assert row["failed"] == 0
        assert row["correct"], row
        assert row["program_gap_max"] <= row["limit"]
        assert row["program_kv_gap_max"] <= row["kv_limit"]
        assert len(row["kv_rows"]) == 2
        assert not row["control_correct"], row
        assert row["control_kv_gap_max"] >= 3 * max(
            row["program_kv_gap_max"], row["kv_limit"])


def test_altered_token_is_not_correct(monkeypatch):
    from nnstreamer_tpu.serving.lm_engine import LMEngine

    cell = harness.load_cell(CELL, TINY)
    sound = harness.run_cell(cell, 6, 2.0, False, time.time(),
                             need_tpu=False)
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"tpot_p90_ms", "out_tokens_per_s",
                                     "setup_s"}
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
    """With ``route_tie`` wide enough that rows are tied, the reference
    tries the second pick at each and keeps the first wherever that is
    what the program served: the run stays correct under the same limits,
    and the count of tied token-layers is printed with the compared
    numbers."""
    cell = harness.load_cell(CELL, TINY)
    cell.config = {**cell.config, "route_tie": 2e-3}
    cell.params = {**cell.params,
                   "check": {**cell.params["check"], "requests": 3,
                             "kv_slots": 1}}
    out = harness.run_cell(cell, 8, 1.0, False, time.time(), need_tpu=False)
    assert out["correct"] is True, out["compared"]
    said = capsys.readouterr().err
    assert "compared route_ties:" in said
    tied = int(said.split("compared route_ties:")[1].split()[0])
    assert tied > 0


def test_second_picks_are_found_where_the_program_took_them(monkeypatch,
                                                            capsys):
    """The reference's own selection score of ONE expert is lifted by 3e-3
    (a stand-in for the rounding that parts program and reference on the
    chip, some thousand times enlarged; one expert, so that three scores
    within the margin stay as rare as they are at 1e-6), so wherever that
    expert lies just under a row's last pick the reference's first pick is
    not the program's. Under a ``route_tie`` that covers the lift every
    such row is tied, the second pick is tried and taken where the program
    took it (with and without the store's rows), and the run is correct
    under the same limits; without the margin it is not."""
    import jax.numpy as jnp

    from benchmark.reference import glm47_flash as ref_mod

    class Lifted(ref_mod.Reference):
        def __init__(self, cfg, seed, mode="reference"):
            super().__init__(cfg, seed, mode)
            self.bias = jnp.zeros((self.m.n_experts,)).at[0].set(3e-3)

    monkeypatch.setattr(ref_mod, "Reference", Lifted)
    cell = harness.load_cell(CELL, TINY)
    cell.params = {**cell.params,
                   "check": {**cell.params["check"], "requests": 8}}
    cell.config = {**cell.config, "route_tie": 6e-3}
    out = harness.run_cell(cell, 9, 1.5, False, time.time(), need_tpu=False)
    said = capsys.readouterr().err
    took = int(said.split("token-layers within")[1].split(",")[1].split()[0])
    assert took >= 2, said
    assert out["correct"] is True, out["compared"]
    cell.config = {**cell.config, "route_tie": 0.0}
    out = harness.run_cell(cell, 9, 1.5, False, time.time(), need_tpu=False)
    assert out["correct"] is False
