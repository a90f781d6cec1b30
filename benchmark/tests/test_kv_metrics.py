"""``kv_read_share`` on hand-made ``LMEngine.stats`` and
``decode_attn_roofline`` on a hand-made reduction of a trace: their
arithmetic, and None where the program has no such counter or kernel (the
parent of the PR that brought them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import trace_reduce
from benchmark.harness import IterationRecord, RequestRecord
from benchmark.layers import decode_attn_roofline, kv_read_share

CONFIG = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 256,
          "vocab_size": 97, "n_positions": 128,
          "engine": {"n_slots": 4, "max_len": 128}}
CELL = SimpleNamespace(config=CONFIG)

OLD = {"prefills": 0, "decode_steps": 0, "slot_steps": 0,
       "wasted_slot_steps": 0}
START = {**OLD, "decode_steps": 80, "kv_rows_attended": 1000}
END = {**OLD, "decode_steps": 180, "kv_rows_attended": 7400}


def stats_ctx(start, end):
    return SimpleNamespace(
        cell=CELL, window=SimpleNamespace(stats_start=start, stats_end=end))


def test_kv_read_share_is_rows_asked_over_rows_of_the_store():
    # 6,400 rows asked in 100 steps of 4 slots x 128 rows
    assert kv_read_share.read(stats_ctx(START, END)) \
        == pytest.approx(6400 * 100.0 / (100 * 4 * 128))
    # the parent's engine has no such counter
    assert kv_read_share.read(stats_ctx(OLD, OLD)) is None
    assert kv_read_share.read(stats_ctx(OLD, END)) is None
    # a window without a decode step
    assert kv_read_share.read(stats_ctx(START, START)) is None


def traced_ctx(ops, peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}):
    """One traced iteration: request 0 (prompt of 10) goes from 3 to 7
    tokens, so 4 kept steps attend 13 + 14 + 15 + 16 = 58 live rows."""
    reqs = [RequestRecord(index=0, due_s=0.0,
                          prompt=np.zeros(10, np.int32), max_new=16)]
    its = [IterationRecord(t0=0.0, t1=1.0, in_window=True, traced=True,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 3, 7)]),
           IterationRecord(t0=1.0, t1=2.0, in_window=True, traced=False,
                           pending_after=0, decode_steps=4,
                           progress=[(0, 7, 11)])]
    trace = None if ops is None else trace_reduce.Reduced(
        window_s=1.0, busy_s=0.5, n_devices=1, ops=ops)
    return SimpleNamespace(
        cell=CELL, peaks=peaks, trace=trace,
        window=SimpleNamespace(requests=reqs, iterations=its),
        adapter=SimpleNamespace(bucket_of=lambda t: 16))


CHUNK = "jit__decode_chunk"
KERNEL = "decode_attention.3 [tpu_custom_call]"


def test_decode_attn_roofline_counts_the_kernel_in_the_chunk_only():
    ops = {(CHUNK, KERNEL): 2e-6,
           (CHUNK, "decode_attention.7 [tpu_custom_call]"): 2e-6,
           (CHUNK, "fusion.12"): 1.0,
           # the quantized configurations' kernel, and a prefill's flash
           (CHUNK, "dequant_gelu_requant.1 [tpu_custom_call]"): 5.0,
           ("jit__prefill_admit", "flash.2 [tpu_custom_call]"): 7.0}
    assert decode_attn_roofline.kernel_seconds(
        traced_ctx(ops).trace) == pytest.approx(4e-6)
    # 58 rows x (2 x 2 layers x 64 x 4 B) = 59,392 B at 1 GB/s = 59.392 us
    # of which the kernel's calls took 4 us... over 100 %: a toy, the
    # arithmetic is what is checked
    kv_row = 2 * 2 * 64 * 4
    want = 58 * kv_row / 1e9 * 100.0 / 4e-6
    assert decode_attn_roofline.read(traced_ctx(ops)) == pytest.approx(want)


def test_decode_attn_roofline_is_silent_without_kernel_trace_or_peaks():
    # the parent: XLA's fusions attend, no Mosaic call in the chunk
    assert decode_attn_roofline.read(
        traced_ctx({(CHUNK, "fusion.12"): 1.0})) is None
    assert decode_attn_roofline.read(traced_ctx(None)) is None
    assert decode_attn_roofline.read(
        traced_ctx({(CHUNK, KERNEL): 1.0}, peaks={})) is None
