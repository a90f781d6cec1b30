"""The reduction, on an event list recorded on the chip (a 17 ms slice of a
traced run of the toy w8a8 cell: two prefills, three decode chunks, the
engine's slot inserts, the Pallas kernel) and on a hand-made list."""

import json
import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_events_tiny_w8a8.json")


@pytest.fixture(scope="module")
def events():
    with open(DATA, encoding="utf-8") as f:
        return json.load(f)


def test_busy_union_and_idle_share_on_the_recorded_slice(events):
    red = tr.reduce_events(events)
    dev = events["devices"]["/device:TPU:0"]
    leaf = [e for e in dev["ops"] if not tr._is_container(e[0])]
    edges = [e[1] for e in events["host"]] \
        + [e[1] + e[2] for e in events["host"]] \
        + [e[1] for e in dev["ops"]] + [e[1] + e[2] for e in dev["ops"]]
    t0, t1 = min(edges), max(edges)
    # an independent union: paint a 10 ns raster
    n = int((t1 - t0) / 10) + 1
    paint = np.zeros(n, bool)
    for _, s, d in leaf:
        paint[int((s - t0) / 10):int(np.ceil((s + d - t0) / 10))] = True
    assert red.window_s == pytest.approx((t1 - t0) / 1e9)
    assert red.busy_s == pytest.approx(paint.sum() * 10 / 1e9, rel=0.05)
    assert red.busy_s <= sum(e[2] for e in leaf) / 1e9
    assert 0.0 < red.busy_s < red.window_s
    idle = sum(red.gaps.values())
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-9)


def test_per_module_time_and_kernel_on_the_recorded_slice(events):
    red = tr.reduce_events(events)
    mods = events["devices"]["/device:TPU:0"]["modules"]
    assert red.module_runs["jit__prefill_admit"] == 2
    assert red.module_runs["jit__decode_chunk"] == 3
    want = sum(d for n, _, d in mods if n.startswith("jit__decode_chunk"))
    assert red.module_seconds("jit__decode_chunk") \
        == pytest.approx(want / 1e9)
    assert red.module_seconds("jit__prefill") == pytest.approx(
        sum(d for n, _, d in mods if n.startswith("jit__prefill")) / 1e9)
    # the Pallas kernel: one call a layer in every prefill and decode step
    assert red.op_seconds("[tpu_custom_call]") > 0
    # every kernel event lies inside a prefill or a decode module
    assert {m for (m, o) in red.ops if "[tpu_custom_call]" in o} \
        <= {"jit__prefill_admit", "jit__decode_chunk"}


def test_gaps_are_labelled_by_host_span_and_next_program(events):
    red = tr.reduce_events(events)
    assert all(" | " in k for k in red.gaps)
    assert any(k.startswith("bench.step_iteration | before jit__decode")
               for k in red.gaps)
    assert any(k.startswith("bench.step_iteration | in jit__decode_chunk")
               for k in red.gaps)
    b = red.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert any(n == "jit__decode_chunk/fusion" for n, _ in b["device_ops"])
    assert any(n.endswith("[tpu_custom_call]") for n, _ in b["device_ops"])
    leaf = sum(s for (_, o), s in red.ops.items() if not tr._is_container(o))
    assert sum(s for _, s in b["device_ops"]) <= leaf * (1 + 1e-9)
    assert all(not tr._is_container(n.split("/", 1)[1])
               for n, _ in b["device_ops"])
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_hand_made_list():
    ev = {
        "host": [["bench.submit", 0.0, 100.0],
                 ["bench.step_iteration", 100.0, 800.0],
                 ["bench.wait_arrival", 900.0, 100.0]],
        "devices": {"/device:TPU:0": {
            "modules": [["jit__prefill_admit(1)", 150.0, 200.0],
                        ["jit__decode_chunk(2)", 500.0, 300.0]],
            "ops": [["fusion.1", 150.0, 100.0],
                    ["fusion.2", 200.0, 150.0],      # overlaps fusion.1
                    ["while.3", 500.0, 300.0],       # a container
                    ["fusion.4", 500.0, 100.0],
                    ["fusion.5", 700.0, 100.0]]}}}
    red = tr.reduce_events(ev)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((200 + 100 + 100) * 1e-9)
    assert red.module_seconds("jit__prefill") == pytest.approx(200e-9)
    assert red.op_seconds("fusion.2") == pytest.approx(150e-9)
    want = {
        "bench.step_iteration | before jit__prefill_admit": 150e-9,
        "bench.step_iteration | before jit__decode_chunk": 150e-9,
        "bench.step_iteration | in jit__decode_chunk": 100e-9,
        "bench.step_iteration | before end of trace": 100e-9,
        "bench.wait_arrival | before end of trace": 100e-9,
    }
    # the gap 0..150 lies under submit (0..100) and step_iteration: its
    # middle (75) is under bench.submit
    got = dict(red.gaps)
    assert got.pop("bench.submit | before jit__prefill_admit") \
        == pytest.approx(150e-9)
    want.pop("bench.step_iteration | before jit__prefill_admit")
    # 800..1000 is one gap; its middle (900) is under wait_arrival
    want.pop("bench.step_iteration | before end of trace")
    want["bench.wait_arrival | before end of trace"] = 200e-9
    assert got == {k: pytest.approx(v) for k, v in want.items()}


def test_short_op_keeps_the_name_and_a_custom_calls_target():
    text = ('%branch_0_fun.9 = (s8[4,32,256]{2,1,0}, f32[4,32,128]{2,1,0}) '
            'custom-call(s32[4,32,256]{2,1,0} %pad.111), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_op(text) == "branch_0_fun.9 [tpu_custom_call]"
    assert tr.short_op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.3"
