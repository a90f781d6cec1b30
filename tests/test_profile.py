"""obs.profile tests: the zero-overhead-when-off hook contract, the
profiler core (ring, samples, jit-cache/compile telemetry, engine
records), the Perfetto export (host + device + serving lanes), the
``/debug/profile`` route on the unified exporter dispatch table, and
the probes roofline helpers backing the MFU gauges."""

import json
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
from nnstreamer_tpu.graph import Pipeline
from nnstreamer_tpu.graph import element as gel
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import profile
from nnstreamer_tpu.obs import tracing
from nnstreamer_tpu.obs.exporter import start_exporter
from nnstreamer_tpu.utils import probes


def tensor_caps(dims, types, rate=30):
    return Caps.tensors(
        TensorsConfig(TensorsInfo.from_strings(dims, types), rate))


@pytest.fixture
def global_metrics():
    """Save/restore the process-global metrics enabled flag."""
    was = obs_metrics.enabled()
    yield obs_metrics.registry()
    (obs_metrics.enable if was else obs_metrics.disable)()


@pytest.fixture
def prof():
    """Profiling off + profiler reset around every test in this file —
    no profiler state leaks between tests or into other files."""
    profile.disable()
    profile.profiler().reset()
    yield profile
    profile.disable()
    profile.profiler().reset()
    profile.profiler().sample_every = profile.DEFAULT_SAMPLE_EVERY
    profile.profiler().resize(profile.DEFAULT_MAX_RECORDS)


@pytest.fixture
def global_tracing():
    was = tracing.enabled()
    tracing.store().reset()
    yield tracing
    tracing.store().reset()
    (tracing.enable if was else tracing.disable)()


def _tiny_pipeline():
    p = Pipeline()
    src = p.add_new("videotestsrc", width=8, height=8, num_buffers=2)
    conv = p.add_new("tensor_converter")
    sink = p.add_new("tensor_sink")
    Pipeline.link(src, conv, sink)
    return p, conv


def _scaler_filter():
    from nnstreamer_tpu.filters.base import FilterProps
    from nnstreamer_tpu.filters.xla import XLAFilter

    f = XLAFilter()
    f.open(FilterProps(
        model="zoo://scaler?dims=4:1&types=float32&scale=2",
        custom="sync=true"))
    return f


def _invoke(f, n=1):
    from nnstreamer_tpu.core.buffer import TensorMemory

    out = None
    for _ in range(n):
        out = f.invoke([TensorMemory(np.ones((1, 4), np.float32))])
    return out


class TestProfileHooks:
    """The chaos-hook pattern: every hook is None while off — disabled
    cost at each consumer is one module-attribute load + None check."""

    def test_hooks_are_none_when_off(self, prof):
        assert profile.DISPATCH_HOOK is None
        assert profile.ENGINE_HOOK is None
        assert profile.KERNEL_HOOK is None
        assert gel.PROFILE_CHAIN_HOOK is None
        assert not profile.enabled()

    def test_enable_installs_and_disable_clears(self, prof):
        p = profile.profiler()
        profile.enable()
        try:
            assert profile.DISPATCH_HOOK is p
            assert profile.ENGINE_HOOK is p
            assert profile.KERNEL_HOOK == p.record_kernel
            assert gel.PROFILE_CHAIN_HOOK == p.profiled_chain
            assert profile.enabled()
        finally:
            profile.disable()
        assert profile.DISPATCH_HOOK is None
        assert profile.ENGINE_HOOK is None
        assert profile.KERNEL_HOOK is None
        assert gel.PROFILE_CHAIN_HOOK is None

    def test_disabled_run_records_nothing(self, prof, global_metrics):
        """Zero per-buffer overhead off: a full pipeline run leaves the
        profiler untouched (nothing was called, not merely filtered)."""
        obs_metrics.disable()
        p, conv = _tiny_pipeline()
        p.run(timeout=30)
        assert profile.profiler().records() == []
        assert profile.profiler().stats()["dispatches"] == 0
        # the structural fast path from test_obs still holds alongside
        assert "_chain_entry" not in conv.__dict__

    def test_disabled_dispatch_skips_profiler(self, prof, global_metrics):
        f = _scaler_filter()
        out = _invoke(f)
        np.testing.assert_array_equal(
            out[0].host(), np.full((1, 4), 2.0, np.float32))
        assert profile.profiler().records() == []

    def test_enabled_chain_hook_times_elements(self, prof, global_metrics):
        obs_metrics.disable()
        profile.enable()
        p, conv = _tiny_pipeline()
        p.run(timeout=30)
        recs = profile.profiler().records("element")
        assert {r["label"] for r in recs} >= {conv.name}
        assert all(r["dur_ns"] >= 0 for r in recs)


class TestProfilerCore:
    def test_ring_is_bounded_and_counts_drops(self, prof):
        p = profile.Profiler(max_records=4)
        for i in range(10):
            p.record_kernel(f"k{i}", (1,), "float32")
        assert len(p.records()) == 4
        assert p.stats()["dropped"] == 6
        assert [r["label"] for r in p.records()] == ["k6", "k7", "k8", "k9"]

    def test_resize_keeps_newest(self, prof):
        p = profile.Profiler(max_records=8)
        for i in range(8):
            p.record_kernel(f"k{i}", (1,), "float32")
        p.resize(3)
        assert [r["label"] for r in p.records()] == ["k5", "k6", "k7"]

    def test_dispatch_records_and_samples(self, prof, global_metrics):
        obs_metrics.enable()
        profile.enable(sample_every=1)   # every dispatch carries a probe
        f = _scaler_filter()
        _invoke(f, n=3)
        p = profile.profiler()
        recs = p.records("dispatch")
        assert len(recs) == 3
        assert all(r["device_ns"] is not None for r in recs)
        # dispatches 2..3 carry the queue-gap since the previous one
        assert sum(r["gap_ns"] is not None for r in recs) == 2
        (s,) = p.samples()
        assert s["n"] == 3 and s["device_n"] == 3
        assert s["shapes"] == ((1, 4),) and s["dtypes"] == ("float32",)
        assert s["mean_host_us"] > 0

    def test_jit_cache_and_compile_telemetry(self, prof, global_metrics):
        obs_metrics.enable()
        profile.enable()

        def jit_counts():
            # the registry is process-global, so assert deltas
            snap = obs_metrics.registry().snapshot()
            fam = snap.get("nnstpu_profile_jit_cache_total",
                           {"series": []})
            return {tuple(s["labels"][k] for k in ("site", "event")):
                    s["value"] for s in fam["series"]}

        before = jit_counts()
        f = _scaler_filter()
        _invoke(f, n=3)
        after = jit_counts()
        # first dispatch misses the per-shape executable cache, the
        # next two hit it
        key_m, key_h = ("executable", "miss"), ("executable", "hit")
        assert after[key_m] - before.get(key_m, 0) == 1
        assert after[key_h] - before.get(key_h, 0) == 2
        snap = obs_metrics.registry().snapshot()
        comp = snap["nnstpu_profile_compile_seconds"]["series"]
        assert any(s["labels"]["site"] == "xla" and s["count"] >= 1
                   for s in comp)
        disp = snap["nnstpu_profile_dispatch_seconds"]["series"]
        assert any(s["labels"] == {"kind": "xla", "clock": "host"}
                   and s["count"] >= 3 for s in disp)

    def test_record_engine_updates_mfu_lane(self, prof, global_metrics):
        obs_metrics.enable()
        profile.enable()
        eng = SimpleNamespace(
            params={"w": np.ones((64, 64), np.float32)}, _engine_label="lm")
        p = profile.profiler()
        p.record_engine(eng, "decode", 0, 10_000_000, tokens=8, steps=8,
                        active=2, queued=1, slots=4)
        assert p.records("engine")[0]["label"] == "lm.decode"
        assert p.records("occupancy")[0]["args"]["active"] == 2
        st = p.stats()["lanes"]["lm"]
        # 2 * 64*64 * 8 tokens over 10ms
        assert st["flops_s"] == pytest.approx(2 * 64 * 64 * 8 / 0.01)
        assert st["intensity"] == pytest.approx(2 * 8 / (4 * 8))

    def test_first_use_interval_is_compile_not_compute(self, prof,
                                                       global_metrics):
        obs_metrics.enable()
        profile.enable()
        eng = SimpleNamespace(
            params={"w": np.ones((8, 8), np.float32)}, _engine_label="lm")
        p = profile.profiler()
        p.record_engine(eng, "prefill", 0, 5_000_000, tokens=4,
                        compiled=True)
        assert "lm" not in p.stats()["lanes"]   # skipped the EWMA
        snap = obs_metrics.registry().snapshot()
        comp = snap["nnstpu_profile_compile_seconds"]["series"]
        assert any(s["labels"]["site"] == "engine" and s["count"] == 1
                   for s in comp)

    def test_dump_samples_roundtrip(self, prof, tmp_path):
        p = profile.Profiler()
        p._record_sample(("lbl", ((1, 4),), ("float32",)), 1000, 900,
                         {"flops": 8.0, "bytes": 32.0}, [])
        path = str(tmp_path / "samples.json")
        assert p.dump_samples(path) == 1
        doc = json.loads(open(path).read())
        assert doc["version"] == 1
        (row,) = doc["samples"]
        assert row["label"] == "lbl" and row["flops"] == 8.0

    def test_report_smoke(self, prof):
        profile.enable()
        profile.profiler().record_kernel("k", (2, 2), "float32")
        assert "records" in profile.report()


class TestPerfettoTrace:
    def test_empty_trace_is_valid_json(self, prof):
        doc = profile.perfetto_trace()
        text = json.dumps(doc)
        assert json.loads(text)["displayTimeUnit"] == "ms"
        assert doc["otherData"]["profile_enabled"] is False
        # process metadata for all four lanes is always present
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"host", "device", "serving", "sched"}

    def test_composite_pipeline_all_three_lane_groups(
            self, prof, global_metrics, global_tracing):
        """Acceptance: a composite (XLA tensor_filter) pipeline run with
        profiling + tracing on yields a Chrome trace with host, device,
        AND serving lanes."""
        tracing.enable()
        profile.enable(sample_every=1)
        p = Pipeline()
        caps = tensor_caps("4:1", "float32")
        src = p.add_new("appsrc", caps=caps,
                        data=[np.ones((1, 4), np.float32)] * 3)
        filt = p.add_new("tensor_filter", framework="xla-tpu",
                         model="zoo://scaler?dims=4:1&types=float32&scale=2")
        sink = p.add_new("tensor_sink")
        Pipeline.link(src, filt, sink)
        p.run(timeout=60)
        # serving lane: engine phases land as serving.* spans
        sp = tracing.store().start_span("serving.prefill",
                                        attrs={"engine": "lm"})
        sp.end()
        doc = profile.perfetto_trace(span_store=tracing.store())
        json.dumps(doc)   # must serialize
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        pids = {e["pid"] for e in slices}
        assert pids >= {1, 2, 3}, f"missing lane group: {pids}"
        host = [e for e in slices if e["pid"] == 1]
        dev = [e for e in slices if e["pid"] == 2]
        srv = [e for e in slices if e["pid"] == 3]
        assert any(e["name"].startswith("tensor_filter") for e in host)
        assert any("scaler" in e["name"] for e in dev)
        assert any(e["args"]["clock"] == "device" for e in dev)
        assert [e["name"] for e in srv] == ["prefill"]
        # every slice timestamp is µs on one shared clock
        assert all(e["ts"] > 0 and e["dur"] >= 0 for e in slices)

    def test_element_records_are_host_lane_fallback(
            self, prof, global_metrics):
        """Tracing off: profiled_chain element records populate pid 1."""
        obs_metrics.disable()
        profile.enable()
        p, conv = _tiny_pipeline()
        p.run(timeout=30)
        doc = profile.perfetto_trace()
        host = [e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["pid"] == 1]
        assert any(e["name"] == conv.name for e in host)

    def test_occupancy_counter_track(self, prof):
        profile.enable()
        eng = SimpleNamespace(params={}, _engine_label="lm")
        profile.profiler().record_engine(
            eng, "decode", 0, 1000, tokens=1, active=3, queued=2, slots=4)
        doc = profile.perfetto_trace()
        (c,) = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert c["name"] == "lm.slots"
        assert c["args"] == {"active": 3, "queued": 2}


class TestExporterProfileRoute:
    def test_debug_profile_serves_trace_json(self, prof, global_metrics):
        profile.enable()
        profile.profiler().record_kernel("k", (1,), "float32")
        with start_exporter(port=0) as exp:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/debug/profile",
                timeout=5).read().decode())
        assert "traceEvents" in doc
        assert doc["otherData"]["profile_enabled"] is True
        assert any(e.get("cat") == "kernel" for e in doc["traceEvents"])

    def test_debug_profile_off_is_still_200(self, prof, global_metrics):
        with start_exporter(port=0) as exp:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/debug/profile",
                timeout=5).read().decode())
        assert doc["otherData"]["profile_enabled"] is False

    def test_404_hint_includes_profile_and_push(self, prof, global_metrics):
        with start_exporter(port=0) as exp:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{exp.port}/nope", timeout=5)
            assert ei.value.code == 404
            hint = ei.value.read().decode()
        # derived from the unified (method, path) table: GET routes
        # bare, POST routes verb-prefixed
        for route in ("/metrics", "/healthz", "/readyz", "/debug/events",
                      "/debug/traces", "/debug/profile",
                      "POST /fleet/push"):
            assert route in hint

    def test_post_still_dispatches_through_shared_table(
            self, prof, global_metrics):
        """Route-table unification regression: POST /fleet/push reaches
        its handler (503 when not aggregating, not 404)."""
        with start_exporter(port=0) as exp:
            req = urllib.request.Request(
                f"http://127.0.0.1:{exp.port}/fleet/push",
                data=b"{}", method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=5)
            assert ei.value.code == 503
            assert "aggregator" in ei.value.read().decode()


#: stands in for a chip: the peak tables key on ``device_kind`` alone
_V5E = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")


class TestEngineGauges:
    def test_lm_engine_run_exposes_mfu_family(self, prof, global_metrics,
                                              monkeypatch):
        """Acceptance: after an LMEngine run with profiling on,
        /metrics carries the nnstpu_profile_mfu family for engine=lm —
        on a device the peak tables know."""
        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.serving import LMEngine

        obs_metrics.enable()
        profile.enable()
        monkeypatch.setattr(  # this CPU has no peak: borrow a v5e's
            profile.Profiler, "_peaks",
            lambda self: (probes.chip_peak_flops(_V5E),
                          probes.chip_peak_hbm_bw(_V5E)))
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(7), 97, 32, 4, 2, 64)
        eng = LMEngine(params, 4, 64, n_slots=2, chunk=4)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new=6)
        assert len(eng.run()[rid]) == 6
        recs = profile.profiler().records("engine")
        # the prompt rode the first chunk's lane: no prefill interval
        assert {r["label"] for r in recs} == {"lm.decode"}
        assert [r["args"]["lane_steps"] for r in recs][:2] == [1, 0]
        with start_exporter(port=0) as exp:
            text = urllib.request.urlopen(exp.url, timeout=5) \
                .read().decode()
        assert 'nnstpu_profile_mfu_ratio{engine="lm"}' in text
        assert 'nnstpu_profile_roofline_ratio{engine="lm"}' in text
        assert 'nnstpu_profile_achieved_flops{engine="lm"}' in text
        mfu = float(next(
            ln.rsplit(" ", 1)[1] for ln in text.splitlines()
            if ln.startswith('nnstpu_profile_mfu_ratio{engine="lm"}')))
        assert 0.0 <= mfu <= 1.0

    def test_no_peak_no_utilization_gauge(self, prof, global_metrics):
        """A device outside the peak tables (this CPU) reports achieved
        FLOP/s but no MFU or roofline ratio: there is no peak to divide
        by, and a made-up one would publish a CPU run as utilization."""
        obs_metrics.enable()
        profile.enable()
        profile.profiler()._update_util("nopeak", 1e9, 1e6, 0.5)
        with start_exporter(port=0) as exp:
            text = urllib.request.urlopen(exp.url, timeout=5) \
                .read().decode()
        assert 'nnstpu_profile_achieved_flops{engine="nopeak"}' in text
        assert 'nnstpu_profile_mfu_ratio{engine="nopeak"}' not in text
        assert 'nnstpu_profile_roofline_ratio{engine="nopeak"}' not in text
        assert "mfu=" not in profile.profiler().report()


class TestProbesRoofline:
    def test_peak_tables_and_ridge(self, prof):
        assert probes.chip_peak_flops(_V5E) == 197e12
        assert probes.chip_peak_hbm_bw(_V5E) == 819e9
        assert probes.ridge_intensity(_V5E) == pytest.approx(
            197e12 / 819e9)

    def test_unknown_device_is_an_error(self, prof):
        """No default peak: utilization on a device the tables do not
        list (the test CPU) raises instead of borrowing v5e's numbers."""
        with pytest.raises(probes.UnknownDeviceError, match="no peak"):
            probes.chip_peak_flops(jax.devices()[0])


class TestCliProfileArgv:
    """Bare --profile/--watchdog must not swallow the pipeline positional
    (argparse consumes nargs="?" values before type conversion rejects
    them); valued and flag-followed forms pass through untouched."""

    def test_bare_flag_defers_past_pipeline(self):
        from nnstreamer_tpu.cli import _normalize_argv

        assert _normalize_argv(["--profile", "videotestsrc ! tensor_sink"]) \
            == ["videotestsrc ! tensor_sink", "--profile"]
        assert _normalize_argv(["--watchdog", "src ! sink"]) \
            == ["src ! sink", "--watchdog"]

    def test_valued_and_flag_followed_forms_untouched(self):
        from nnstreamer_tpu.cli import _normalize_argv

        for argv in (["--profile", "16", "pipe"],
                     ["--profile", "--trace", "pipe"],
                     ["--watchdog", "2.5", "pipe"],
                     ["--profile"]):
            assert _normalize_argv(argv) == argv
