"""obs.tracing: span store semantics (tail retention, trees, sampling),
cross-wire context propagation through the query protocol (ISSUE
satellite: one client→server round trip — including a >CHUNK_SIZE
chunked payload — yields ONE trace whose server-side spans parent onto
the client span, and the disabled path adds no ``trace`` key to wire
meta), serving-engine spans, the ``/debug/*`` exposition endpoints,
and the PipelineTracer report ordering satellite."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
from nnstreamer_tpu.graph import Pipeline
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.obs import tracing
from nnstreamer_tpu.obs.exporter import start_exporter
from nnstreamer_tpu.obs.tracing import (NOOP_SPAN, SpanStore, ctx_from_wire)
from nnstreamer_tpu.query.protocol import (Cmd, recv_message, send_message)
from nnstreamer_tpu.serving import LMEngine


@pytest.fixture
def tracing_on():
    was = tracing.enabled()
    tracing.store().reset()
    tracing.enable()
    yield tracing.store()
    (tracing.enable if was else tracing.disable)()
    tracing.store().sample_every = 1
    tracing.store().reset()


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --------------------------------------------------------------------------- #
# SpanStore unit semantics
# --------------------------------------------------------------------------- #

class TestSpanStore:
    def test_disabled_store_returns_shared_noop(self):
        store = SpanStore(enabled=False)
        s = store.start_span("pipeline.buffer")
        assert s is NOOP_SPAN and s.context is None and not s.recording
        s.set_attribute("k", 1)
        s.end()  # all no-ops
        assert store.summaries() == []

    def test_tree_nests_children_under_local_parents(self):
        store = SpanStore(enabled=True)
        root = store.start_span("pipeline.buffer", attrs={"source": "src"})
        child = store.start_span("pipeline.element", parent=root.context,
                                 attrs={"element": "conv"})
        grand = store.start_span("query.request", parent=child.context)
        grand.end()
        child.end()
        root.end()
        tid = root.context.trace_id
        assert child.context.trace_id == tid
        tree = store.tree(tid)
        assert tree["spans"] == 3
        (r,) = tree["tree"]
        assert r["name"] == "pipeline.buffer" and r["parent_id"] is None
        (c,) = r["children"]
        assert c["name"] == "pipeline.element"
        assert c["children"][0]["name"] == "query.request"
        # summaries: one completed trace rooted at pipeline.buffer
        (summ,) = store.summaries()
        assert summ["trace_id"] == tid and summ["completed"]
        assert summ["root"] == "pipeline.buffer"

    def test_remote_parented_spans_surface_as_tree_roots(self):
        store = SpanStore(enabled=True)
        remote = ctx_from_wire({"tid": "aa" * 8, "sid": "bb" * 8})
        s = store.start_span("query.server_handle", parent=remote)
        s.end()
        tree = store.tree("aa" * 8)
        assert tree["tree"][0]["name"] == "query.server_handle"
        assert tree["tree"][0]["parent_id"] == "bb" * 8
        # remote-parented halves never complete locally
        assert store.summaries()[0]["completed"] is False

    def test_min_ms_filter_keeps_only_slow_completed(self):
        store = SpanStore(enabled=True)
        slow = store.start_span("query.request")
        slow.start_ns -= int(50e6)  # pretend it started 50 ms ago
        slow.end()
        fast = store.start_span("query.request")
        fast.end()
        all_traces = store.summaries()
        assert len(all_traces) == 2
        slow_only = store.summaries(min_ms=25.0)
        assert [t["trace_id"] for t in slow_only] == \
            [slow.context.trace_id]

    def test_head_sampling_admits_one_in_n(self):
        store = SpanStore(enabled=True, sample_every=4)
        admitted = sum(store.should_sample() for _ in range(40))
        assert admitted == 10

    def test_slowest_retention_survives_wraparound_concurrent(self):
        """Acceptance criterion: slowest-N retention survives ring
        wraparound under concurrent span recording."""
        store = SpanStore(max_traces=32, keep_slowest=4, enabled=True)
        slow_ids = []
        for i in range(4):
            s = store.start_span("query.request", attrs={"i": i})
            s.start_ns -= int((i + 1) * 1e9)  # 1..4 s — the tail
            s.end()
            slow_ids.append(s.context.trace_id)

        def hammer(n):
            for _ in range(n):
                s = store.start_span("pipeline.buffer")
                store.start_span("pipeline.element",
                                 parent=s.context).end()
                s.end()

        threads = [threading.Thread(target=hammer, args=(200,))
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # 1600 fast traces flooded a 32-slot ring; the 4 slow ones must
        # still be retrievable, marked retained, and ranked slowest-first
        summ = store.summaries()
        assert len(summ) <= store.max_traces + store.keep_slowest
        kept = {t["trace_id"] for t in summ}
        assert set(slow_ids) <= kept
        assert [t["trace_id"] for t in summ[:4]] == slow_ids[::-1]
        assert all(t["slowest_retained"] for t in summ[:4])
        for tid in slow_ids:
            assert store.tree(tid) is not None

    def test_span_context_manager_sets_current_and_flags_error(self):
        store = SpanStore(enabled=True)
        assert tracing.current_context() is None
        with pytest.raises(RuntimeError):
            with store.start_span("serving.request") as s:
                assert tracing.current_context() is s.context
                raise RuntimeError("boom")
        assert tracing.current_context() is None
        assert s.attrs.get("error") is True
        assert s.end_ns is not None


# --------------------------------------------------------------------------- #
# Wire-level propagation (protocol only)
# --------------------------------------------------------------------------- #

class TestWireMeta:
    @staticmethod
    def _pipe():
        return socket.socketpair()

    def test_disabled_adds_no_trace_key(self):
        assert not tracing.enabled()  # suite default
        a, b = self._pipe()
        send_message(a, Cmd.DATA, {"k": 1}, b"x")
        cmd, meta, payload = recv_message(b)
        assert meta == {"k": 1}  # bit-identical meta: no added wire bytes
        a.close(); b.close()

    def test_enabled_without_current_context_adds_no_key(self, tracing_on):
        a, b = self._pipe()
        send_message(a, Cmd.DATA, {"k": 1}, b"x")
        _, meta, _ = recv_message(b)
        assert "trace" not in meta
        a.close(); b.close()

    def test_current_context_rides_wire_and_parses(self, tracing_on):
        a, b = self._pipe()
        with tracing.start_span("query.request") as span:
            send_message(a, Cmd.DATA, {"k": 1}, b"x")
        _, meta, _ = recv_message(b)
        ctx = ctx_from_wire(meta["trace"])
        assert ctx.trace_id == span.context.trace_id
        assert ctx.span_id == span.context.span_id
        a.close(); b.close()

    def test_chunked_transfer_carries_context(self, tracing_on):
        from nnstreamer_tpu.query.protocol import CHUNK_SIZE

        a, b = self._pipe()
        payload = b"\x5a" * (CHUNK_SIZE + 100)
        result = {}

        def rx():
            result["msg"] = recv_message(b)

        t = threading.Thread(target=rx, daemon=True)
        t.start()
        # send on the span's own thread — contextvars are thread-local,
        # exactly as in the client element's chain call
        with tracing.start_span("query.request") as span:
            send_message(a, Cmd.DATA, {"k": 2}, payload)
        t.join(10)
        cmd, meta, got = result["msg"]
        assert cmd is Cmd.DATA and got == payload
        assert ctx_from_wire(meta["trace"]).trace_id == \
            span.context.trace_id
        # the chunked receive recorded a query.recv span in the trace
        names = [s.name for s in
                 tracing.store().spans_of(span.context.trace_id)]
        assert "query.recv" in names
        a.close(); b.close()


# --------------------------------------------------------------------------- #
# End-to-end: client pipeline → wire → server pipeline → back
# --------------------------------------------------------------------------- #

def _roundtrip(dims, n_bufs, payload_elems):
    port = free_port()
    sp = Pipeline("server")
    ssrc = sp.add_new("tensor_query_serversrc", host="127.0.0.1",
                      port=port, id=0, dims=dims, types="float32")
    filt = sp.add_new("tensor_filter", model=lambda x: x * 2)
    ssink = sp.add_new("tensor_query_serversink", id=0)
    Pipeline.link(ssrc, filt, ssink)
    sp.start()
    try:
        time.sleep(0.2)
        caps = Caps.tensors(TensorsConfig(
            TensorsInfo.from_strings(dims, "float32"), 30))
        cp = Pipeline("client")
        src = cp.add_new(
            "appsrc", caps=caps,
            data=[np.full((1, payload_elems), i, np.float32)
                  for i in range(n_bufs)])
        qc = cp.add_new("tensor_query_client", host="127.0.0.1", port=port)
        sink = cp.add_new("tensor_sink", store=True)
        Pipeline.link(src, qc, sink)
        cp.run(timeout=120)
        assert sink.num_buffers == n_bufs
    finally:
        sp.stop()
    return sp, cp


class TestCrossWirePropagation:
    def test_roundtrip_yields_single_trace_with_server_spans(
            self, tracing_on):
        _roundtrip("4:1", 3, 4)
        completed = [t for t in tracing_on.summaries() if t["completed"]]
        assert len(completed) == 3  # one trace per source buffer
        for t in completed:
            assert t["root"] == "pipeline.buffer"
            spans = tracing_on.spans_of(t["trace_id"])
            by_name = {}
            for s in spans:
                by_name.setdefault(s.name, []).append(s)
            # client side: root, element chains, the offload request +
            # sends in both directions; server side: the adopted handler
            # and its pipeline elements — ONE trace id spans it all
            for name in ("pipeline.buffer", "pipeline.element",
                         "query.request", "query.send",
                         "query.server_handle"):
                assert name in by_name, f"missing {name}: {by_name.keys()}"
            (req,) = by_name["query.request"]
            (srv,) = by_name["query.server_handle"]
            # the server span parents onto the CLIENT request span
            assert srv.context.parent_id == req.context.span_id
            assert srv.context.trace_id == req.context.trace_id
            # server pipeline elements hang below the handler span
            # (auto-numbered names: tensor_filter<N> etc.)
            elements = {str(s.attrs.get("element")) for s in
                        by_name["pipeline.element"]}
            assert any(e.startswith("tensor_filter") for e in elements)
            assert any(e.startswith("tensor_query_serversink")
                       for e in elements)
            # full tree is rooted once (everything reachable from the
            # client root — nothing floats)
            tree = tracing_on.tree(t["trace_id"])
            assert len(tree["tree"]) == 1
            assert tree["tree"][0]["name"] == "pipeline.buffer"

    def test_chunked_roundtrip_is_one_trace(self, tracing_on):
        from nnstreamer_tpu.query.protocol import CHUNK_SIZE

        elems = CHUNK_SIZE // 4  # 1 MiB of float32 + flex header → chunked
        _roundtrip(f"{elems}:1", 1, elems)
        completed = [t for t in tracing_on.summaries() if t["completed"]]
        assert len(completed) == 1
        names = {s.name for s in
                 tracing_on.spans_of(completed[0]["trace_id"])}
        # chunked assembly records query.recv on BOTH halves, still in
        # the same single trace
        assert {"pipeline.buffer", "query.request", "query.recv",
                "query.server_handle"} <= names

    def test_disabled_roundtrip_records_nothing(self):
        assert not tracing.enabled()
        tracing.store().reset()
        _roundtrip("4:1", 2, 4)
        assert tracing.store().summaries() == []


# --------------------------------------------------------------------------- #
# Serving engine spans
# --------------------------------------------------------------------------- #

V, D, H, L, MAXLEN = 32, 16, 2, 1, 32


def _engine():
    params = causal_lm.init_causal_lm(
        jax.random.PRNGKey(0), V, D, H, L, MAXLEN)
    return LMEngine(params, H, MAXLEN, n_slots=2, chunk=4)


class TestServingSpans:
    def test_request_span_tree_covers_lifecycle(self, tracing_on):
        eng = _engine()
        eng.submit(np.arange(1, 5, dtype=np.int32), max_new=4)
        eng.run()
        # the engine's iterations are traces of their own
        # (tests/test_step_phases.py); this test is about the request's
        completed = [t for t in tracing_on.summaries()
                     if t["completed"] and t["root"] != "serving.step"]
        assert len(completed) == 1
        assert completed[0]["root"] == "serving.request"
        tree = tracing_on.tree(completed[0]["trace_id"])
        (root,) = tree["tree"]
        child_names = {c["name"] for c in root["children"]}
        # first-ever bucket use also records the compile span
        assert {"serving.admission_wait", "serving.prefill",
                "serving.compile", "serving.decode"} <= child_names
        assert root["attrs"]["tokens"] == 4

    def test_submit_joins_callers_current_trace(self, tracing_on):
        eng = _engine()
        with tracing.start_span("query.request") as outer:
            eng.submit(np.arange(1, 5, dtype=np.int32), max_new=2)
        eng.run()
        spans = tracing_on.spans_of(outer.context.trace_id)
        req = [s for s in spans if s.name == "serving.request"]
        assert len(req) == 1
        assert req[0].context.parent_id == outer.context.span_id

    def test_disabled_requests_carry_no_spans(self):
        assert not tracing.enabled()
        eng = _engine()
        eng.submit(np.arange(1, 5, dtype=np.int32), max_new=2)
        req = eng._queue[0]
        assert req.span is None and req.wait_span is None
        eng.run()
        assert tracing.store().summaries() == []


# --------------------------------------------------------------------------- #
# /debug exposition endpoints
# --------------------------------------------------------------------------- #

def _get_json(url):
    return json.loads(urllib.request.urlopen(url, timeout=10).read())


class TestDebugEndpoints:
    def test_traces_tree_and_pipeline_endpoints(self, tracing_on):
        sp, cp = _roundtrip("4:1", 2, 4)
        with start_exporter(port=0, enable=False) as exp:
            base = f"http://{exp.host}:{exp.port}"
            listing = _get_json(f"{base}/debug/traces")
            assert listing["tracing_enabled"] is True
            traces = listing["traces"]
            assert len([t for t in traces if t["completed"]]) == 2
            tid = traces[0]["trace_id"]
            tree = _get_json(f"{base}/debug/traces/{tid}")
            assert tree["trace_id"] == tid and tree["spans"] > 0
            names = set()

            def walk(nodes):
                for n in nodes:
                    names.add(n["name"])
                    walk(n["children"])

            walk(tree["tree"])
            assert "query.request" in names
            # min_ms high-pass filters everything out
            empty = _get_json(f"{base}/debug/traces?min_ms=1e9")
            assert empty["traces"] == []
            # unknown id → 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(f"{base}/debug/traces/nope")
            assert ei.value.code == 404
            # live topology + per-element span stats (pipelines held
            # alive by the locals above — the registry is a WeakSet)
            dbg = _get_json(f"{base}/debug/pipeline")
            pipe_names = {p["name"] for p in dbg["pipelines"]}
            assert {"server", "client"} <= pipe_names
            client = next(p for p in dbg["pipelines"]
                          if p["name"] == "client")
            kinds = {e["kind"] for e in client["elements"]}
            assert "tensor_query_client" in kinds
            assert any(e["links"] for e in client["elements"])
            assert dbg["element_spans"]  # per-element span aggregates
            for st in dbg["element_spans"].values():
                assert st["n"] >= 1 and st["max_us"] >= st["mean_us"] >= 0

    def test_bad_min_ms_is_400(self, tracing_on):
        with start_exporter(port=0, enable=False) as exp:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(f"http://{exp.host}:{exp.port}"
                          "/debug/traces?min_ms=abc")
            assert ei.value.code == 400


# --------------------------------------------------------------------------- #
# PipelineTracer consumers (report-ordering satellite + span store)
# --------------------------------------------------------------------------- #

class TestPipelineTracer:
    @staticmethod
    def _traced_run(spans=False):
        from nnstreamer_tpu.utils.trace import PipelineTracer

        p = Pipeline()
        src = p.add_new("videotestsrc", width=8, height=8, num_buffers=3)
        conv = p.add_new("tensor_converter")
        slow = p.add_new("tensor_filter",
                         model=lambda x: (time.sleep(0.01), x)[1])
        sink = p.add_new("tensor_sink")
        Pipeline.link(src, conv, slow, sink)
        tracer = PipelineTracer.attach(p, spans=spans)
        p.run(timeout=60)
        return tracer

    def test_report_rows_sorted_by_mean_proctime_desc(self):
        tracer = self._traced_run()
        lines = tracer.report().splitlines()
        assert len(lines) >= 4  # header + 3 non-source elements
        proctimes = [float(ln.split()[2]) for ln in lines[1:]]
        assert proctimes == sorted(proctimes, reverse=True)
        # chain proctime is inclusive of downstream pushes, so the slow
        # filter must rank above the sink it feeds (and its own mean
        # must carry the deliberate 10 ms sleep)
        names = [ln.split()[0] for ln in lines[1:]]
        filt = next(i for i, n in enumerate(names)
                    if n.startswith("tensor_filter"))
        sink = next(i for i, n in enumerate(names)
                    if n.startswith("tensor_sink"))
        assert filt < sink
        # the mean must carry the sleep (well above a no-op chain call)
        assert proctimes[filt] >= 1_000  # us

    def test_span_consumer_uses_private_store(self):
        assert not tracing.enabled()
        tracer = self._traced_run(spans=True)
        report = tracer.span_report()
        assert "tensor_filter" in report
        stats = tracer.span_store.element_stats()
        assert any(k.startswith("tensor_filter") for k in stats)
        # private means private: the global store saw nothing
        assert tracing.store().summaries() == []

    def test_span_report_requires_spans_attach(self):
        tracer = self._traced_run(spans=False)
        with pytest.raises(RuntimeError, match="spans=True"):
            tracer.span_report()


# --------------------------------------------------------------------------- #
# device_trace ↔ trace-id join
# --------------------------------------------------------------------------- #

def test_device_trace_links_xprof_to_trace(tmp_path, tracing_on,
                                           monkeypatch):
    from nnstreamer_tpu.utils import trace as utrace

    calls = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda logdir: calls.setdefault("start", logdir))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.setdefault("stop", True))
    with tracing.start_span("query.request") as outer:
        with utrace.device_trace(str(tmp_path)) as dt:
            pass
    assert calls == {"start": str(tmp_path), "stop": True}
    assert dt.trace_id == outer.context.trace_id
    spans = tracing_on.spans_of(outer.context.trace_id)
    dev = [s for s in spans if s.name == "device.xprof"]
    assert len(dev) == 1
    assert dev[0].attrs["logdir"] == str(tmp_path)
    assert dev[0].context.parent_id == outer.context.span_id
