"""The Gated DeltaNet family's step counter (``steps/qwen3_next_block.py``)
and the readers of its kernels and counter, on hand-made records and a
hand-made reduction of a trace: their arithmetic, exactly one counter for
each configuration, and None where the program has no such counter or
kernel (the parent of the PR that brought them) or the cell's
configuration is of another family."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, steps, trace_reduce
from benchmark.harness import IterationRecord, RequestRecord
from benchmark.layers import (decode_hbm_roofline, decode_mfu,
                              decode_step_ms, gdn_state_roofline,
                              gqa_attn_roofline, held_expert_hit_share)
from benchmark.steps import glm_experts, gpt2_block
from benchmark.steps import qwen3_next_block as q3


def config_of(name):
    with open(os.path.join(harness.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY = config_of("tiny-qwen3next-selftest")
FULL = config_of("qwen3-next-80b-a3b")
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
START = {"decode_steps": 80, "experts_hit": 1000, "held_picks": 500,
         "picks": 2000, "state_rows": 400, "lane_steps": 20}
# 100 steps: 8 hits, 3 slot-layers (1 of them the lane's) and a quarter of
# the picks held here a step
END = {"decode_steps": 180, "experts_hit": 1800, "held_picks": 1000,
       "picks": 4000, "state_rows": 700, "lane_steps": 70}
OPS = {("jit__decode_chunk", "gated_delta.3 [tpu_custom_call]"): 0.002,
       ("jit__decode_chunk", "gated_delta.4 [tpu_custom_call]"): 0.002,
       ("jit__decode_chunk", "decode_attention.7 [tpu_custom_call]"): 0.001,
       ("jit__decode_chunk", "fusion.12"): 0.015,
       ("jit__other", "gated_delta.3 [tpu_custom_call]"): 1.0}


def ctx_of(config=TINY, ops=OPS, start=START, end=END, modules=None):
    """One traced iteration of 4 steps: request 0 (prompt of 10) goes from
    3 to 7 tokens (4 kept rows attend 13 + 14 + 15 + 16 = 58 stored
    tokens), request 1 (prompt of 5) gets its first token and one more (5
    prompt rows attend 15, one kept row 6)."""
    reqs = [RequestRecord(index=0, due_s=0.0,
                          prompt=np.zeros(10, np.int32), max_new=16),
            RequestRecord(index=1, due_s=0.0,
                          prompt=np.zeros(5, np.int32), max_new=16)]
    its = [IterationRecord(t0=0.0, t1=1.0, in_window=True, traced=True,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 3, 7), (1, 0, 2)])]
    trace = None if ops is None else trace_reduce.Reduced(
        window_s=1.0, busy_s=0.5, n_devices=1, ops=ops,
        modules=modules or {"jit__decode_chunk": 0.02})
    return SimpleNamespace(
        cell=SimpleNamespace(config=config), peaks=PEAKS, trace=trace,
        window=SimpleNamespace(requests=reqs, iterations=its,
                               stats_start=start, stats_end=end))


@pytest.mark.parametrize("name,counter", [
    ("cerebras-gpt-1.3b", gpt2_block), ("glm-4.7-flash", glm_experts),
    ("qwen3-next-80b-a3b", q3), ("tiny-qwen3next-selftest", q3)])
def test_each_configuration_is_counted_by_exactly_one_module(name, counter):
    config = config_of(name)
    assert [m for m in steps.counters() if not steps.missing(m, config)] \
        == [counter]


def test_the_published_sizes_count_as_the_configuration_reckons_them():
    b = FULL["bytes"]
    s = q3.sizes(FULL)
    assert q3.linear_mixer_params(s) == \
        b["per_layer_parameters"]["gated_deltanet_mixer"]
    assert q3.full_mixer_params(s) == \
        b["per_layer_parameters"]["full_attention_mixer"]
    assert q3.expert_bytes(FULL) == 4 * 3_145_728
    assert q3.state_bytes(FULL) * 64 * 6 == b["state_bytes"]
    assert q3.conv_bytes(FULL) * 64 * 6 == b["conv_state_bytes"]
    assert q3.kv_bytes_per_token(FULL) * 64 * 2048 == b["kv_bytes"]
    # everything but the 8 x 64 routed experts, the embedding's gathered
    # rows left out
    assert q3.fixed_weight_bytes_per_step(FULL) == 4 * (
        b["parameters"] - 8 * 64 * 3_145_728 - 18_992 * 2_048)


def test_shares_are_least_time_over_measured_time():
    ctx = ctx_of()
    assert q3.traced(ctx) == q3.Traced(0.02, 4, 5, 58 + 6, 5, 15)
    assert decode_step_ms.read(ctx) == pytest.approx(5.0)
    flops = 5 * q3.row_flops(TINY, True) + 5 * q3.row_flops(TINY, False) \
        + 10 * 4 * 0.25 * q3.held_pick_flops(TINY) \
        + (64 + 15) * q3.attended_row_flops(TINY)
    assert decode_mfu.read(ctx) == pytest.approx(
        flops * 100.0 / (0.02 * 1e12))
    nbytes = 4 * (q3.fixed_weight_bytes_per_step(TINY)
                  + 8 * q3.expert_bytes(TINY)
                  + 3 * 2 * (q3.state_bytes(TINY) + q3.conv_bytes(TINY))) \
        + (64 + 15) * q3.kv_bytes_per_token(TINY)
    assert decode_hbm_roofline.read(ctx) == pytest.approx(
        max(nbytes / 1e9, flops / 1e12) * 100.0 / 0.02)
    # the kernels: their own calls inside the module, and no other's;
    # the lane's slot-layer (3 linear layers, half a lane step a step) is
    # not the kernel's
    assert gdn_state_roofline.read(ctx) == pytest.approx(
        4 * (3 - 0.5 * 3) * 2 * q3.state_bytes(TINY) / 1e9 * 100.0 / 0.004)
    assert gqa_attn_roofline.read(ctx) == pytest.approx(
        64 * q3.kv_bytes_per_token(TINY) / 1e9 * 100.0 / 0.001)
    # 8 hits a step of 4 layers x 4 held experts
    assert held_expert_hit_share.read(ctx) == pytest.approx(50.0)


READERS = (gdn_state_roofline, gqa_attn_roofline, held_expert_hit_share)


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
@pytest.mark.parametrize("other", ["cerebras-gpt-1.3b", "glm-4.7-flash"])
def test_none_on_another_familys_cell(reader, other):
    assert reader.read(ctx_of(config=config_of(other))) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_none_where_there_is_nothing_to_read(reader):
    old = {"decode_steps": 80, "lane_steps": 20}
    # a program without the counters
    if reader is not gqa_attn_roofline:
        assert reader.read(ctx_of(start=old, end=old)) is None
    if reader is not held_expert_hit_share:
        # an untraced run; a trace without the module or the kernel
        assert reader.read(ctx_of(ops=None)) is None
        assert reader.read(ctx_of(modules={"jit__x": 1.0})) is None
        assert reader.read(ctx_of(
            ops={("jit__decode_chunk", "fusion.1"): 0.5})) is None


def test_the_whole_step_without_the_counters_reads_nothing_of_the_bytes():
    old = {"decode_steps": 80}
    assert decode_hbm_roofline.read(ctx_of(start=old, end=old)) is None
    assert decode_mfu.read(ctx_of(start=old, end=old)) is None
