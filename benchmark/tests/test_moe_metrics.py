"""The whole step's readers on the family with experts, and the readers of
its kernels and counter, on hand-made records and a hand-made reduction of
a trace: their arithmetic, and None where the program has no such counter
or kernel (the parent of the PR that brought them) or, for the kernels and
the counter, the cell's configuration is not of this family."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import flops_glm47 as fg
from benchmark import harness, trace_reduce
from benchmark.harness import IterationRecord, RequestRecord
from benchmark.layers import (decode_hbm_roofline, decode_mfu,
                              decode_step_ms, expert_gemm_roofline,
                              expert_hit_share, latent_attn_roofline)
from benchmark.steps import glm_experts as moe_step

with open(os.path.join(harness.HERE, "configs",
                       "tiny-glm47-selftest.json")) as f:
    TINY = json.load(f)
with open(os.path.join(harness.HERE, "configs", "glm-4.7-flash.json")) as f:
    FULL = json.load(f)
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
START = {"decode_steps": 80, "experts_hit": 1000}
END = {"decode_steps": 180, "experts_hit": 2200}     # 12 a step


def ctx_of(config=TINY, ops=None, start=START, end=END, modules=None):
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
                           progress=[(0, 3, 7), (1, 0, 2)]),
           IterationRecord(t0=1.0, t1=2.0, in_window=True, traced=False,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 7, 11)])]
    trace = None if ops is None else trace_reduce.Reduced(
        window_s=1.0, busy_s=0.5, n_devices=1, ops=ops,
        modules=modules or {"jit__decode_chunk": 0.02})
    return SimpleNamespace(
        cell=SimpleNamespace(config=config), peaks=PEAKS, trace=trace,
        window=SimpleNamespace(requests=reqs, iterations=its,
                               stats_start=start, stats_end=end))


OPS = {("jit__decode_chunk", "moe_expert_gemm.3 [tpu_custom_call]"): 0.004,
       ("jit__decode_chunk", "moe_expert_gemm.4 [tpu_custom_call]"): 0.004,
       ("jit__decode_chunk", "mla_decode_attention.7 [tpu_custom_call]"):
           0.001,
       ("jit__decode_chunk", "fusion.12"): 0.011,
       ("jit__other", "moe_expert_gemm.3 [tpu_custom_call]"): 1.0}


def test_the_published_sizes_count_as_the_issue_reckons_them():
    assert fg.expert_bytes(FULL) == 37_748_736
    assert fg.latent_bytes_per_row(FULL) == 2_304
    # 84,677,888 + 4 x 31,331,648 parameters outside the routed experts,
    # the final norm and the head
    assert fg.fixed_weight_bytes_per_step(FULL) == 4 * (
        84_677_888 + 4 * 31_331_648 + 2_048 + 154_880 * 2_048)


def test_the_traced_work_is_counted_from_the_harness_records():
    got = moe_step.traced(ctx_of(ops=OPS))
    assert got == moe_step.Traced(0.02, 4, 5, 58 + 6, 5, 15)
    assert decode_step_ms.read(ctx_of(ops=OPS)) == pytest.approx(5.0)
    assert moe_step.hit_per_step(ctx_of(ops=OPS)) == pytest.approx(12.0)


def test_shares_are_least_time_over_measured_time():
    ctx = ctx_of(ops=OPS)
    flops = 5 * fg.row_flops(TINY, True) + 5 * fg.row_flops(TINY, False) \
        + (64 + 15) * fg.attended_row_flops(TINY)
    assert decode_mfu.read(ctx) == pytest.approx(
        flops * 100.0 / (0.02 * 1e12))
    nbytes = 4 * (fg.fixed_weight_bytes_per_step(TINY)
                  + 12 * fg.expert_bytes(TINY)) \
        + (64 + 15) * 3 * fg.latent_bytes_per_row(TINY)
    assert decode_hbm_roofline.read(ctx) == pytest.approx(
        max(nbytes / 1e9, flops / 1e12) * 100.0 / 0.02)
    # the kernels: their own calls inside the module, and no other's
    assert expert_gemm_roofline.read(ctx) == pytest.approx(
        4 * 12 * fg.expert_bytes(TINY) / 1e9 * 100.0 / 0.008)
    assert latent_attn_roofline.read(ctx) == pytest.approx(
        64 * 3 * fg.latent_bytes_per_row(TINY) / 1e9 * 100.0 / 0.001)
    # 1,200 hits in 100 steps of 2 expert layers x 8 experts
    assert expert_hit_share.read(ctx) == pytest.approx(75.0)


READERS = (decode_step_ms, decode_mfu, decode_hbm_roofline,
           expert_gemm_roofline, latent_attn_roofline, expert_hit_share)


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_none_where_there_is_nothing_to_read(reader):
    gpt2 = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 256,
            "vocab_size": 97, "n_positions": 128}
    old = {"decode_steps": 80}
    # another family's cell: the whole step's readers count it by that
    # family's counter (test_whole_step.py), these have nothing to read
    if reader in (expert_gemm_roofline, latent_attn_roofline,
                  expert_hit_share):
        assert reader.read(ctx_of(config=gpt2, ops=OPS)) is None
    # a program without the counter
    if reader is not decode_step_ms and reader is not decode_mfu \
            and reader is not latent_attn_roofline:
        assert reader.read(ctx_of(ops=OPS, start=old, end=old)) is None
    if reader is not expert_hit_share:
        # an untraced run; a trace without the module or the kernels
        assert reader.read(ctx_of(ops=None)) is None
        assert reader.read(ctx_of(ops=OPS, modules={"jit__x": 1.0})) is None
    if reader in (expert_gemm_roofline, latent_attn_roofline):
        assert reader.read(ctx_of(
            ops={("jit__decode_chunk", "fusion.1"): 0.5})) is None
