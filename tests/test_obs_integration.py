"""End-to-end observability: run a real element chain, a query
server+client pair, and an LMEngine workload with metrics enabled,
then scrape the live ``/metrics`` endpoint and assert at least one
populated series from each of the three instrumented layers
(the ISSUE acceptance criterion)."""

import re
import socket
import time
import urllib.request

import numpy as np
import pytest

import jax

from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
from nnstreamer_tpu.graph import Pipeline
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs.exporter import start_exporter
from nnstreamer_tpu.serving import LMEngine

V, D, H, L, MAXLEN = 32, 16, 2, 1, 32

#: exposition line: comment, or  name{labels} value  /  name value
_LINE_RE = re.compile(
    r"^(?:#.*|[A-Za-z_:][A-Za-z0-9_:]*(?:\{[^{}]*\})? [0-9+\-.eEinf]+)$")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def metrics_on():
    was = obs_metrics.enabled()
    obs_metrics.enable()
    yield obs_metrics.registry()
    (obs_metrics.enable if was else obs_metrics.disable)()


def _run_element_chain():
    p = Pipeline()
    src = p.add_new("videotestsrc", width=8, height=8, num_buffers=3)
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", model=lambda x: x)
    sink = p.add_new("tensor_sink")
    Pipeline.link(src, conv, filt, sink)
    p.run(timeout=60)


def _run_query_roundtrip():
    port = free_port()
    sp = Pipeline("server")
    ssrc = sp.add_new("tensor_query_serversrc", host="127.0.0.1",
                      port=port, id=0, dims="4:1", types="float32")
    filt = sp.add_new("tensor_filter", model=lambda x: x * 2)
    ssink = sp.add_new("tensor_query_serversink", id=0)
    Pipeline.link(ssrc, filt, ssink)
    sp.start()
    try:
        time.sleep(0.2)
        caps = Caps.tensors(TensorsConfig(
            TensorsInfo.from_strings("4:1", "float32"), 30))
        cp = Pipeline("client")
        src = cp.add_new("appsrc", caps=caps,
                         data=[np.full((1, 4), i, np.float32)
                               for i in range(3)])
        qc = cp.add_new("tensor_query_client", host="127.0.0.1", port=port)
        sink = cp.add_new("tensor_sink", store=True)
        Pipeline.link(src, qc, sink)
        cp.run(timeout=60)
        assert sink.num_buffers == 3
        return qc.name
    finally:
        sp.stop()


def _run_engine_workload():
    params = causal_lm.init_causal_lm(
        jax.random.PRNGKey(0), V, D, H, L, MAXLEN)
    eng = LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)
    rids = [eng.submit(np.arange(1, 5 + i, dtype=np.int32), max_new=4)
            for i in range(2)]
    res = eng.run()
    assert all(len(res[r]) == 4 for r in rids)


def _series(text, family):
    """Sample lines of `family` (incl. _bucket/_sum/_count children)."""
    return [ln for ln in text.splitlines()
            if ln.startswith(family) and not ln.startswith("#")]


def test_all_three_layers_visible_in_one_scrape(metrics_on):
    _run_element_chain()
    _run_query_roundtrip()
    _run_engine_workload()

    with start_exporter(port=0) as exp:
        text = urllib.request.urlopen(exp.url, timeout=10).read().decode()

    # every non-empty line is valid exposition syntax
    for ln in text.splitlines():
        assert _LINE_RE.match(ln), f"malformed exposition line: {ln!r}"

    # pipeline layer: per-element buffer counts + proctime histogram
    assert _series(text, "nnstpu_pipeline_buffers_total")
    assert _series(text, "nnstpu_pipeline_proctime_seconds_bucket")

    # query layer: messages by direction/cmd and an RTT histogram
    msgs = _series(text, "nnstpu_query_messages_total")
    assert any('direction="sent"' in ln for ln in msgs)
    assert any('direction="recv"' in ln for ln in msgs)
    assert _series(text, "nnstpu_query_bytes_total")
    assert _series(text, "nnstpu_query_roundtrip_seconds_count")

    # serving layer: stream lifecycle, TTFT, token throughput
    streams = _series(text, "nnstpu_serving_streams_total")
    assert any('event="admitted"' in ln for ln in streams)
    assert any('event="completed"' in ln for ln in streams)
    assert _series(text, "nnstpu_serving_ttft_seconds_count")
    assert _series(text, "nnstpu_serving_tokens_total")


def test_engine_slot_gauges_live_and_release(metrics_on):
    _run_engine_workload()
    snap = obs_metrics.registry().snapshot()
    slots = {tuple(s["labels"].items()): s["value"]
             for s in snap["nnstpu_serving_active_slots"]["series"]}
    # workload has drained; the weakref gauge reads 0 (or the engine is
    # already collected and the callback degrades to 0) — never raises
    assert slots[(("engine", "lm"),)] == 0
    prefills = snap["nnstpu_serving_prefills_total"]["series"]
    assert sum(s["value"] for s in prefills) >= 2


def test_query_inflight_gauge_registered(metrics_on):
    name = _run_query_roundtrip()
    snap = obs_metrics.registry().snapshot()
    # this client's series: clients of other tests in the same process
    # may still be registered, with requests of their own in flight
    depth = [s for s in snap["nnstpu_query_inflight_depth"]["series"]
             if s["labels"]["element"] == name]
    assert depth and all(s["value"] == 0 for s in depth)  # drained at EOS
    rec = snap["nnstpu_query_reconnects_total"]["series"]
    assert sum(s["value"] for s in rec) >= 1
