"""One whole-step share, one step time and one roofline for every cell: the
three merged readers on both families' toy configurations read what the
family's own pair read before the merge (the arithmetic written out here);
a configuration that no counter of ``steps/`` counts raises when its cell
is loaded; ``out_tokens_per_s`` counts the tokens stamped inside the window
and not those of the drain; the lane's two readers; and what
``BENCHMARK.json`` has to keep so that a later cell can carry a claim."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import flops, harness, steps, trace_reduce
from benchmark import flops_glm47 as fg
from benchmark.end_to_end import out_tokens_per_s
from benchmark.harness import IterationRecord, RequestRecord
from benchmark.layers import (decode_hbm_roofline, decode_mfu,
                              decode_step_ms, lane_fill_share,
                              lane_step_share)
from benchmark.steps import glm_experts, gpt2_block

ROOT = harness.CHECKOUT
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}


def config_of(name):
    with open(os.path.join(harness.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


GPT2 = config_of("tiny-selftest")
GLM = config_of("tiny-glm47-selftest")


def ctx_of(config, secs=0.02, stats=({"decode_steps": 80, "experts_hit": 1000},
                                     {"decode_steps": 180,
                                      "experts_hit": 2200})):
    """One traced iteration of 4 steps: request 0 (prompt of 10) goes from
    3 to 7 tokens (4 kept rows attend 13 + 14 + 15 + 16 = 58 stored
    tokens), request 1 (prompt of 5) gets its first token and one more (5
    prompt rows attend 15, one kept row 6); an untraced one beside it."""
    reqs = [RequestRecord(index=0, due_s=0.0,
                          prompt=np.zeros(10, np.int32), max_new=16),
            RequestRecord(index=1, due_s=0.0,
                          prompt=np.zeros(5, np.int32), max_new=16)]
    its = [IterationRecord(t0=0.0, t1=1.0, in_window=True, traced=True,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 3, 7), (1, 0, 2)]),
           IterationRecord(t0=1.0, t1=2.0, in_window=True, traced=False,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 7, 11)])]
    trace = trace_reduce.Reduced(
        window_s=1.0, busy_s=0.5, n_devices=1,
        ops={("jit__decode_chunk", "fusion.12"): secs},
        modules={"jit__decode_chunk": secs})
    return SimpleNamespace(
        cell=SimpleNamespace(config=config), peaks=PEAKS, trace=trace,
        adapter=SimpleNamespace(bucket_of=lambda t: 16),
        window=SimpleNamespace(requests=reqs, iterations=its,
                               stats_start=stats[0], stats_end=stats[1]))


def old_gpt2_pair(config):
    """What ``decode_step_ms``, ``decode_mfu`` and ``decode_hbm_roofline``
    read before the merge, from ``flops.py`` alone: 5 kept slot-steps (4 of
    request 0 from row 13 on, 1 of request 1 at row 6) in 4 steps."""
    m = flops.model_dims(config)
    dims = (m["d_model"], m["n_layers"], m["vocab"], m["d_ff"])
    fl = flops.decode_flops(1, 12, 4, *dims) \
        + flops.decode_flops(1, 5, 1, *dims)
    nbytes = 4 * flops.weight_bytes_per_step(config) \
        + (58 + 6) * flops.kv_bytes_per_token(config)
    return (0.02 * 1e3 / 4, fl * 100.0 / (0.02 * 1e12),
            max(nbytes / 1e9, fl / 1e12) * 100.0 / 0.02)


def old_moe_pair(config):
    """What ``moe_decode_step_ms``, ``moe_decode_mfu`` and
    ``moe_decode_hbm_roofline`` read, from ``flops_glm47.py`` alone: 5 kept
    rows and 5 prompt rows that attended 64 + 15 stored tokens, 12 experts
    hit a step."""
    fl = 5 * fg.row_flops(config, True) + 5 * fg.row_flops(config, False) \
        + (64 + 15) * fg.attended_row_flops(config)
    nbytes = 4 * (fg.fixed_weight_bytes_per_step(config)
                  + 12 * fg.expert_bytes(config)) \
        + (64 + 15) * int(config["num_hidden_layers"]) \
        * fg.latent_bytes_per_row(config)
    return (0.02 * 1e3 / 4, fl * 100.0 / (0.02 * 1e12),
            max(nbytes / 1e9, fl / 1e12) * 100.0 / 0.02)


FAMILIES = [(GPT2, gpt2_block, old_gpt2_pair), (GLM, glm_experts,
                                                old_moe_pair)]


@pytest.mark.parametrize("config,counter,old", FAMILIES,
                         ids=["tiny-selftest", "tiny-glm47-selftest"])
def test_merged_readers_read_what_the_familys_pair_read(config, counter,
                                                        old):
    assert steps.counter(config) is counter
    ctx = ctx_of(config)
    step_ms, mfu, roofline = old(config)
    assert decode_step_ms.read(ctx) == step_ms
    assert decode_mfu.read(ctx) == mfu
    assert decode_hbm_roofline.read(ctx) == roofline
    # an untraced run, and a trace without the module
    ctx.trace = None
    assert decode_step_ms.read(ctx) is None and decode_mfu.read(ctx) is None
    assert decode_hbm_roofline.read(ctx) is None
    assert decode_mfu.read(ctx_of(config, secs=0.0)) is None


def test_the_quantized_toy_is_counted_as_its_block():
    assert steps.counter(config_of("tiny-selftest-w8a8")) is gpt2_block


@pytest.mark.parametrize("name", ["cerebras-gpt-1.3b", "glm-4.7-flash",
                                  "cerebras-gpt-1.3b-w8a8"])
def test_every_committed_configuration_has_one_counter(name):
    assert steps.counter(config_of(name)) in (gpt2_block, glm_experts)


def test_a_configuration_no_counter_counts_raises_by_name(tmp_path):
    bare = {"name": "mamba-toy", "hidden_size": 64, "vocab_size": 256}
    with pytest.raises(ValueError) as e:
        steps.counter(bare)
    # each counter is named with the keys it misses
    assert "gpt2_block lacks ['n_embd'" in str(e.value)
    assert "glm_experts lacks" in str(e.value)
    assert "'num_experts_per_tok'" in str(e.value)
    assert "mamba-toy" in str(e.value)
    # both families' keys in one file: no guess is made
    with pytest.raises(ValueError, match="more than one"):
        steps.counter({**GPT2, **GLM})
    # and a cell of it fails when it is loaded, before anything runs
    (tmp_path / "bare.json").write_text(json.dumps(bare))
    bench = {"configs": [{"name": "bare", "file": os.path.relpath(
                 tmp_path / "bare.json", ROOT)}],
             "workloads": [{"name": "tiny_selftest", "config": "bare",
                            "traffic": "tiny_selftest", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "slot_waste_share", "unit": "%"},
                           {"name": "decode_mfu", "unit": "%"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="no counter in benchmark/steps"):
        harness.load_cell("tiny_selftest", str(tmp_path / "BENCHMARK.json"))


def test_out_tokens_per_s_counts_the_window_and_not_the_drain():
    def req(i, stamps):
        r = RequestRecord(index=i, due_s=0.0,
                          prompt=np.zeros(4, np.int32), max_new=len(stamps))
        r.token_s = list(stamps)
        return r

    reqs = [req(0, [0.5, 0.5, 1.0, 1.5]),       # all inside
            req(1, [1.5, 2.0, 2.25, 3.0]),      # 2.0 is the close: inside
            req(2, [2.5, 2.5])]                 # the drain's
    ctx = SimpleNamespace(window=SimpleNamespace(seconds=2.0, requests=reqs))
    assert out_tokens_per_s.read(ctx) == 6 / 2.0
    # a window in which no token appeared reports nothing, never 0
    ctx.window.requests = reqs[2:]
    assert out_tokens_per_s.read(ctx) is None


OLD = {"prefills": 0, "decode_steps": 0}
START = {"prefills": 10, "decode_steps": 80, "lane_steps": 16,
         "lane_rows": 1024, "lane_tokens": 700}
END = {"prefills": 14, "decode_steps": 880, "lane_steps": 216,
       "lane_rows": 13824, "lane_tokens": 10300}
LANE = [(lane_step_share, 200 / 800 * 100.0),
        (lane_fill_share, 9600 / 12800 * 100.0)]


@pytest.mark.parametrize("reader,want", LANE,
                         ids=["lane_step_share", "lane_fill_share"])
def test_lane_readers(reader, want):
    def ctx(a, b):
        return SimpleNamespace(
            window=SimpleNamespace(stats_start=a, stats_end=b))

    assert reader.read(ctx(START, END)) == pytest.approx(want)
    # an engine that prefills whole prompts has no such counters
    assert reader.read(ctx(OLD, OLD)) is None
    assert reader.read(ctx(OLD, END)) is None
    # a window in which the base did not move
    assert reader.read(ctx(START, START)) is None


def test_toy_window_prices_the_lane():
    cell = harness.load_cell("tiny_selftest",
                             "benchmark/tests/data/BENCHMARK.tiny.json")
    adapter, sched = harness.set_up(cell, 2**31 + 35, 1.5)
    window = harness.run_window(adapter, sched, 1.5)
    ctx = harness.Context(cell, window, 0.0, {}, adapter)
    steps_share = lane_step_share.read(ctx)
    fill = lane_fill_share.read(ctx)
    assert 0.0 < steps_share <= 100.0 and 0.0 < fill <= 100.0
    # every prompt token went through the lane, in windows of LANE_ROWS
    d = {k: window.stats_end[k] - window.stats_start[k]
         for k in ("lane_tokens", "lane_rows", "lane_steps")}
    assert 0 < d["lane_tokens"] <= sum(int(r.prompt.size)
                                       for r in window.requests)
    assert d["lane_rows"] == d["lane_steps"] * 64
    # tokens of the window over its seconds, the drain's left out
    rate = out_tokens_per_s.read(ctx)
    total = sum(len(r.token_s) for r in window.requests)
    assert 0 < rate * 1.5 <= total


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_one_whole_step_share_and_it_names_no_cell(bench):
    mfu = [m for m in bench["per_layer"] if "mfu" in m["name"]]
    assert [m["name"] for m in mfu] == ["decode_mfu"]
    assert "workloads" not in mfu[0]
    assert mfu[0]["unit"] == "%" and mfu[0]["better"] == "higher"
    # the step's time and roofline go with it, for every cell
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in ("decode_step_ms", "decode_hbm_roofline"):
        assert "workloads" not in by[name]
    assert not [n for n in by if n.startswith("moe_decode_")
                or n == "prefill_ms_per_ktok"]


def test_every_listed_workload_is_a_cell_that_reports_what_it_moves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_every_cell_loads_with_a_counter_a_reader_and_its_files(bench):
    pairs = set()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert steps.counter(cell.config)
        assert {m["name"] for m in cell.per_layer} >= {
            "decode_mfu", "decode_step_ms", "decode_hbm_roofline"}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2
        for kind, rows in (("end_to_end", cell.end_to_end),
                           ("layers", cell.per_layer)):
            for m in rows:
                assert callable(harness.reader_of(kind, m["name"]).read)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
