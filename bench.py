"""Benchmark: MobileNet-v2 224×224 streaming classification pipeline.

Mirrors BASELINE.md's headline config (videotestsrc ! tensor_converter !
tensor_filter framework=xla-tpu model=mobilenet_v2 ! tensor_decoder
mode=image_labeling ! sink) end-to-end on the real TPU chip. A device
that utils/probes has no peak for (a CPU) is an error: nothing here runs
on one under device metric names, except the same-host CPU comparator
children, which say so.

Reported (BASELINE.md "numbers to produce"):
  * ``value``/``fps_median`` — steady-state pipeline FPS; the headline
    throughput run repeats BENCH_REPEATS (default 3) times and reports
    the median-of-medians with min/max spread (round 5 saw 89-205 FPS
    run-to-run on identical code — single shots are noise);
  * ``p50_invoke_us`` — synchronous per-invoke latency (reference
    tensor_filter.c:366-380 ``latency`` prop contract: includes transfer);
  * ``split`` (+ per-config ``*_split``) — amortized per-frame
    H2D/compute/D2H + one-shot RTT (utils/probes.phase_split) for the
    headline AND the SSD/DeepLab/PoseNet configs;
  * ``mfu`` — model FLOPs (XLA cost analysis) × FPS / chip peak;
  * ``batch_sweep`` — frames-per-tensor batch 8..128 FPS+MFU curve (+ a
    w8-quant point): the compute-bound operating point and its knee;
  * ``transformer_prefill_*`` — causal-LM prefill scoring pipeline
    (bf16 params, 1K context): tokens/sec + MFU, the MXU-saturating row;
  * ``vs_baseline`` — speedup over the STRONGEST same-host jax-CPU run
    (best of per-frame and batch-8 serving, subprocess); falls back to
    FPS/30 (real-time camera rate) if the CPU run fails;
  * extras: SSD / DeepLab / PoseNet FPS (peak + median), adaptive
    micro-batching. (That the paths run on the chip at all is
    chip_smoke.py's job, not a lane here.)

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import faulthandler
import json
import os
import signal
import statistics
import subprocess
import sys
import time

import numpy as np

faulthandler.register(signal.SIGUSR1)  # live stack dump for debugging

#: partial results, flushed by the watchdog if a phase wedges (a stuck
#: device must degrade the bench to partial numbers, not to rc=124 silence)
_partial: dict = {}


def _arm_watchdog() -> None:
    # r5: the full lane set (extras + sweep splits + decode) measured
    # ~1700s on-chip; 1500 clipped the tail of the r5 self-run
    budget = float(os.environ.get("BENCH_BUDGET_SECS", "2400"))
    if budget <= 0:
        return

    import threading

    def fire() -> None:
        _partial.setdefault("metric", "mobilenet_v2_224_pipeline_fps")
        _partial.setdefault("value", None)
        _partial.setdefault("unit", "frames/sec")
        _partial.setdefault("vs_baseline", None)
        _partial["watchdog_timeout_s"] = budget
        print(json.dumps(_sanitize(_partial)), flush=True)
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(3)

    t = threading.Timer(budget, fire)
    t.daemon = True
    t.start()

#: env overrides let the harness be validated on CPU with a tiny model;
#: the driver's TPU run uses the defaults
SIZE = int(os.environ.get("BENCH_SIZE", "224"))
MODEL = os.environ.get(
    "BENCH_MODEL", f"zoo://mobilenet_v2?width=1.0&size={SIZE}")
CLASSES = int(os.environ.get("BENCH_CLASSES", "1001"))
#: max in-flight frames at the decode boundary. The decoder drains frames
#: the moment their readback lands (readiness-based), so depth only needs
#: to cover readback latency / per-frame-host-time; 64 was sized for the
#: ~70-130 ms round trip of the round-5 installation, at ~1-2 ms/frame of
#: host work, and costs negligible memory.
DECODE_DEPTH = int(os.environ.get("BENCH_DEPTH", "64"))
#: (V, D, H, L) of the bench LM — shared by the main prefill lane and the
#: long-context lane; the longctx MFU extrapolation anchors on the main
#: lane's FLOPs count, which is only valid when the model dims match
_LM_DIMS = (8192, 1024, 16, 8)


def build_pipeline(frames, labels_path, sync: bool):
    from nnstreamer_tpu.graph import Pipeline

    p = Pipeline("bench")
    src = p.add_new("appsrc", caps=_video_caps(), data=frames)
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", framework="xla-tpu", model=MODEL,
                     custom="sync=true" if sync else "")
    # pipelined decode: keep D2H readbacks in flight (readback RTT, not TPU
    # compute, bounds streaming FPS — see tensor_decoder async_depth)
    dec = p.add_new("tensor_decoder", mode="image_labeling", option1=labels_path,
                    async_depth=4 if sync else DECODE_DEPTH)
    sink = p.add_new("tensor_sink")
    Pipeline.link(src, conv, filt, dec, sink)
    return p, filt, sink


def _video_caps():
    from fractions import Fraction

    from nnstreamer_tpu.core import Caps

    return Caps("video/x-raw", {"format": "RGB", "width": SIZE, "height": SIZE,
                                "framerate": Fraction(0, 1)})


def _windowed_fps(arrivals, n_warmup: int, tail: int, window: int = 64):
    """(peak, median) FPS over sliding ``window``-frame windows, excluding
    warmup head and the EOS drain tail (a window overlapping the EOS burst
    would overstate steady-state throughput)."""
    ts = np.asarray(arrivals[n_warmup:len(arrivals) - tail])
    win = min(window, len(ts) - 1)
    if win <= 0:
        return float("nan"), float("nan")
    spans = ts[win:] - ts[:-win]
    if not len(spans) or spans.min() <= 0:
        return float("nan"), float("nan")
    return win / spans.min(), win / float(np.median(spans))


def _pipeline_fps(model_spec: str, size: int, dec_mode: str, dec_opts: dict,
                  n_frames: int = 160, n_warmup: int = 16,
                  adaptive_batch: int = 0):
    """Steady-state FPS of a videotestsrc → converter → filter → decoder
    pipeline (BASELINE.md 'numbers to produce' configs). With
    ``adaptive_batch=N`` the serving path runs through
    tensor_batch/tensor_unbatch (one H2D + one invoke per group)."""
    from nnstreamer_tpu.graph import Pipeline

    p = Pipeline()
    src = p.add_new("videotestsrc", width=size, height=size,
                    num_buffers=n_warmup + n_frames, pattern="random")
    conv = p.add_new("tensor_converter")
    chain = [src, conv]
    if adaptive_batch > 1:
        # budget must cover the source-rate group fill time (see the
        # adaptive-SSD note in _extra_benches / docs/performance.md)
        chain.append(p.add_new("tensor_batch", max_batch=adaptive_batch,
                               budget_ms=200.0))
        model_spec = _with_batch(model_spec, adaptive_batch)
    filt = p.add_new("tensor_filter", framework="xla-tpu", model=model_spec)
    chain.append(filt)
    if adaptive_batch > 1:
        chain.append(p.add_new("tensor_unbatch"))
    dec = p.add_new("tensor_decoder", mode=dec_mode, async_depth=DECODE_DEPTH,
                    **dec_opts)
    sink = p.add_new("tensor_sink")
    arrivals = []
    sink.new_data = lambda buf: arrivals.append(time.monotonic())
    Pipeline.link(*chain, dec, sink)
    p.run(timeout=600)
    return _windowed_fps(arrivals, n_warmup, DECODE_DEPTH)


def _extra_benches(tmpdir: str) -> dict:
    """SSD/DeepLab/PoseNet pipeline FPS (reference model sizes)."""
    import traceback

    from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

    priors = os.path.join(tmpdir, "box_priors.txt")
    write_box_priors(priors, size=300)
    labels91 = os.path.join(tmpdir, "coco.txt")
    with open(labels91, "w") as f:
        f.write("\n".join(f"c{i}" for i in range(91)))
    configs = {
        "ssd_mobilenet_300_fps": (
            "zoo://ssd_mobilenet_v2?size=300&num_classes=91", 300,
            "bounding_box",
            dict(option1="mobilenet-ssd", option2=labels91, option3=priors,
                 option4="300:300", option5="300:300")),
        "deeplab_v3_257_fps": (
            "zoo://deeplab_v3?size=257&num_classes=21", 257,
            "image_segment", dict(option1="tflite-deeplab")),
        "posenet_257_fps": (
            "zoo://posenet?size=257", 257,
            "pose_estimation",
            dict(option1="514:514", option2="257:257",
                 option4="heatmap-offset")),
    }
    out = {}
    for key, (spec, size, mode, opts) in configs.items():
        try:
            _mark(f"extra bench {key} starting")
            peak, med = _pipeline_fps(spec, size, mode, opts)
            out[key] = round(peak, 2)
            out[key.replace("_fps", "_fps_median")] = round(med, 2)
            out[key.replace("_fps", "_split")] = _config_split(spec, size)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            out[key] = None
        _partial.update(out)  # stream rows as they land (watchdog-visible)
    try:
        # detection through the adaptive serving path: batched H2D+invoke
        # with the per-frame device-NMS decode restored after unbatch.
        # budget_ms must exceed the time the source takes to FILL a group
        # (8 frames at ~120 FPS ≈ 68 ms): r3 used 50 ms, so every group
        # flushed partial at ~6 frames and was padded to 8 — 25% wasted
        # invoke compute, measured BELOW the unbatched path. See
        # docs/performance.md (adaptive batching: budget vs fill time).
        _mark("extra bench ssd adaptive batch starting")
        spec, size, mode, opts = configs["ssd_mobilenet_300_fps"]
        peak, med = _pipeline_fps(spec, size, mode, opts, adaptive_batch=8)
        out["ssd_mobilenet_300_adaptive8_fps"] = round(peak, 2)
        out["ssd_mobilenet_300_adaptive8_fps_median"] = round(med, 2)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        out["ssd_mobilenet_300_adaptive8_fps"] = None
    _partial.update(out)
    return out


def _config_split(spec: str, size: int, batch: int = 1, k: int = 16,
                  device=None):
    """Per-config phase split (says in one run whether a
    config is invoke-, transfer-, or host-bound). ``batch>1`` probes the
    batched operating points of the sweep."""
    import jax

    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.utils import probes

    try:
        bundle = get_model(spec)
        example = np.zeros((batch, size, size, 3), np.uint8)
        return probes.phase_split(bundle.fn(), [example],
                                  device=device or jax.devices()[0], k=k)
    except Exception:
        import traceback

        traceback.print_exc(file=sys.stderr)
        return None


def _composite_bench() -> dict:
    """BASELINE.md composite row: tensor_mux + repo-LSTM loop served
    behind tensor_query offload; a localhost client measures end-to-end
    FPS and per-frame round-trip p50 (send→result, matched by offset)."""
    import socket
    import traceback

    try:
        from nnstreamer_tpu.core import Caps
        from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
        from nnstreamer_tpu.elements.repo import reset_repo
        from nnstreamer_tpu.graph import Pipeline

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        reset_repo()
        n_frames, warm = 192, 16
        feats, d_in = 64, 32
        sp = Pipeline("bench-lstm-server")
        ssrc = sp.add_new("tensor_query_serversrc", host="127.0.0.1",
                          port=port, id=0, dims=f"{d_in}:1",
                          types="float32")
        state = sp.add_new("tensor_reposrc", slot_index=77,
                           dims=f"{feats}:1,{feats}:1",
                           types="float32,float32")
        mux = sp.add_new("tensor_mux", sync_mode="nosync")
        filt = sp.add_new("tensor_filter", framework="xla-tpu",
                          model=f"zoo://lstm_cell?features={feats}"
                                f"&input_size={d_in}")
        demux = sp.add_new("tensor_demux", tensorpick="0,1:2")
        qo, qs = sp.add_new("queue"), sp.add_new("queue")
        ssink = sp.add_new("tensor_query_serversink", id=0, async_depth=32)
        rsink = sp.add_new("tensor_reposink", slot_index=77)
        Pipeline.link(ssrc, mux)
        Pipeline.link(state, mux)
        Pipeline.link(mux, filt, demux)
        Pipeline.link(demux, qo, ssink)   # y → back to the client
        Pipeline.link(demux, qs, rsink)   # (h', c') → loop
        sp.start()
        time.sleep(0.3)

        caps = Caps.tensors(TensorsConfig(
            TensorsInfo.from_strings(f"{d_in}:1", "float32")))
        rng = np.random.default_rng(0)

        # phase 1 — true per-frame round trip: SYNC client (depth=1), so
        # each measurement is send→result with no queueing delay
        sync_n = 24
        rtts: list = []
        cp = Pipeline("bench-lstm-client-sync")
        send_t = {"t": 0.0}

        def sync_gen():
            for _ in range(sync_n):
                send_t["t"] = time.monotonic()
                yield rng.normal(size=(1, d_in)).astype(np.float32)

        src = cp.add_new("appsrc", caps=caps, data=sync_gen())
        qc = cp.add_new("tensor_query_client", host="127.0.0.1", port=port)
        sink = cp.add_new("tensor_sink")
        sink.new_data = lambda b: rtts.append(time.monotonic() - send_t["t"])
        Pipeline.link(src, qc, sink)
        cp.run(timeout=300)

        # phase 2 — throughput: pipelined client+server (async_depth) so
        # the per-frame device RTT overlaps instead of serializing
        cp2 = Pipeline("bench-lstm-client")
        src2 = cp2.add_new("appsrc", caps=caps, data=(
            rng.normal(size=(1, d_in)).astype(np.float32)
            for _ in range(n_frames + warm)))
        qc2 = cp2.add_new("tensor_query_client", host="127.0.0.1",
                          port=port, async_depth=32)
        sink2 = cp2.add_new("tensor_sink")
        arrivals: list = []
        sink2.new_data = lambda b: arrivals.append(time.monotonic())
        Pipeline.link(src2, qc2, sink2)
        cp2.run(timeout=600)
        sp.stop()
        if len(arrivals) < warm + 32:
            return {}
        peak, med = _windowed_fps(arrivals, warm, 0, window=32)
        p50 = float(np.percentile(np.asarray(rtts[4:]) * 1e6, 50)) \
            if len(rtts) > 8 else None
        row = {"composite_lstm_query_fps": round(peak, 2),
               "composite_lstm_query_fps_median": round(med, 2),
               "composite_roundtrip_p50_us":
                   round(p50, 1) if p50 else None}
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _with_batch(model_spec: str, batch: int) -> str:
    return model_spec + ("&" if "?" in model_spec else "?") + f"batch={batch}"


def _adaptive_bench(labels_path: str) -> dict:
    """Adaptive micro-batched serving (tensor_batch/tensor_unbatch): the
    per-frame stream is grouped up to max_batch within a latency budget,
    runs ONE H2D + ONE invoke per group, and is restored to per-frame
    buffers. Unlike the frames-per-tensor row this measures the TRUE
    serving path: per-frame in, per-frame out."""
    import traceback

    try:
        from nnstreamer_tpu.graph import Pipeline

        batch = 16
        n_frames, warm, depth = 480, 32, 64
        p = Pipeline()
        src = p.add_new("videotestsrc", width=SIZE, height=SIZE,
                        num_buffers=n_frames + warm, pattern="random")
        conv = p.add_new("tensor_converter")
        bat = p.add_new("tensor_batch", max_batch=batch, budget_ms=200.0)
        filt = p.add_new("tensor_filter", framework="xla-tpu",
                         model=_with_batch(MODEL, batch))
        unb = p.add_new("tensor_unbatch")
        dec = p.add_new("tensor_decoder", mode="image_labeling",
                        option1=labels_path, async_depth=depth)
        sink = p.add_new("tensor_sink")
        arrivals = []
        sink.new_data = lambda buf: arrivals.append(time.monotonic())
        Pipeline.link(src, conv, bat, filt, unb, dec, sink)
        p.run(timeout=600)
        peak, med = _windowed_fps(arrivals, warm, depth)
        if not np.isfinite(peak):
            return {}
        row = {"adaptive_batch16_fps": round(peak, 2),
               "adaptive_batch16_fps_median": round(med, 2)}
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _epilogue_fusion_lane(device) -> dict:
    """Epilogue fusion (ops/epilogue.py) on the composite detection
    pipeline: ssd_mobilenet → identity tensor_transform → bounding_box
    decoder, fused (post-chain compiled into the filter's jit: one XLA
    dispatch per frame, D2H ships the NMS'd (K,6) rows) vs unfused
    (filter + transform + decoder device-reduce each dispatch
    separately). Dispatches-per-frame comes from the profiler's
    kind="dispatch" records — the same accounting obs/profile.py uses —
    so the claimed collapse is measured, not inferred. Output is
    bit-identical between the two runs (pinned by tests/test_epilogue.py);
    this lane only measures rate and dispatch count."""
    import tempfile
    import traceback

    try:
        from nnstreamer_tpu.graph import Pipeline
        from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors
        from nnstreamer_tpu.obs import profile as _prof

        size, n_frames, warm = 300, 160, 16
        with tempfile.TemporaryDirectory() as td:
            priors = os.path.join(td, "box_priors.txt")
            write_box_priors(priors, size=size)

            def run(auto_fuse):
                _prof.enable()
                _prof.profiler().reset()
                p = Pipeline()
                p.auto_fuse = auto_fuse
                src = p.add_new("videotestsrc", width=size, height=size,
                                num_buffers=warm + n_frames,
                                pattern="random")
                conv = p.add_new("tensor_converter")
                filt = p.add_new(
                    "tensor_filter", framework="xla-tpu",
                    model=f"zoo://ssd_mobilenet_v2?size={size}"
                          f"&num_classes=91")
                # value-neutral post stage (same-dtype typecast): gives
                # the fuser a transform to absorb and the unfused run an
                # honest extra per-frame dispatch to count
                tpost = p.add_new("tensor_transform", mode="typecast",
                                  option="float32")
                dec = p.add_new("tensor_decoder", mode="bounding_box",
                                option1="mobilenet-ssd", option3=priors,
                                option4=f"{size}:{size}",
                                option5=f"{size}:{size}",
                                async_depth=DECODE_DEPTH)
                sink = p.add_new("tensor_sink")
                arrivals = []
                sink.new_data = lambda buf: arrivals.append(time.monotonic())
                Pipeline.link(src, conv, filt, tpost, dec, sink)
                p.run(timeout=600)
                dispatches = len(_prof.profiler().records(kind="dispatch"))
                _prof.disable()
                _, med = _windowed_fps(arrivals, warm, DECODE_DEPTH)
                dpf = dispatches / max(len(arrivals), 1)
                return med, dpf, p._epilogue_count

            _mark("epilogue fusion lane: fused run starting")
            fused_med, fused_dpf, n_stages = run(True)
            _mark("epilogue fusion lane: unfused run starting")
            unfused_med, unfused_dpf, _ = run(False)
        row = {
            "epilogue_fusion_fps_median": round(fused_med, 2),
            "epilogue_fusion_unfused_fps_median": round(unfused_med, 2),
            "epilogue_fusion_speedup": round(fused_med / unfused_med, 3)
            if unfused_med else None,
            "epilogue_fusion_dispatches_per_frame": round(fused_dpf, 3),
            "epilogue_fusion_unfused_dispatches_per_frame":
                round(unfused_dpf, 3),
            "epilogue_fusion_dispatch_ratio":
                round(unfused_dpf / fused_dpf, 3) if fused_dpf else None,
            "epilogue_fusion_stages_fused": n_stages,
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _autotune_lane(device) -> dict:
    """Autotuner (tune/) cold→warm proof on the flash-attention block
    knob. Cold run: empty store, one bounded measured sweep over the
    round-5 hand-sweep candidate grid. Warm run: the store reloads from
    disk (a restarted instance) and the same call resolves with ZERO
    sweeps — ``autotune_warm_sweeps`` must stay 0. The tuner's pick is
    then timed against the hand-set 512/1024 default on the same shape:
    ``autotune_flash_vs_hand`` >= 1 means the closed loop matched or
    beat the hand sweep it replaces."""
    import tempfile
    import traceback

    try:
        import jax.numpy as jnp

        from nnstreamer_tpu import tune
        from nnstreamer_tpu.ops.pallas.flash_attention import (
            _DEFAULT_BLOCKS, flash_attention)

        on_cpu = device.platform == "cpu"
        # interpret-mode flash is orders slower: shrink the sweep shape
        # on CPU so the lane proves the mechanism, not the hardware
        B, H, L, D = (1, 2, 256, 64) if on_cpu else (4, 8, 2048, 128)
        q = jnp.ones((B, H, L, D), jnp.float32)
        k = jnp.ones((B, H, L, D), jnp.float32)
        v = jnp.ones((B, H, L, D), jnp.float32)

        def timed(reps=5, **kw):
            flash_attention(q, k, v, **kw).block_until_ready()  # warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                flash_attention(q, k, v, **kw).block_until_ready()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts)) * 1e3

        tune.disable(save=False)
        with tempfile.TemporaryDirectory() as td:
            store = os.path.join(td, "tune.json")
            # -- cold: empty store pays the one bounded sweep ---------
            _mark("autotune lane: cold sweep starting")
            tn = tune.enable(store, fit_from_profiler=False)
            flash_attention(q, k, v).block_until_ready()
            cold_sweeps = tn.stats["sweeps"]
            cold_trials = tn.stats["trials"]
            picked = tn.store.entries()
            blocks = next(iter(picked.values()))["value"] if picked \
                else list(_DEFAULT_BLOCKS)
            tune.disable()  # persists the store

            # -- warm: fresh tuner, same disk store, zero sweeps ------
            _mark("autotune lane: warm run starting")
            tn = tune.enable(store, fit_from_profiler=False)
            flash_attention(q, k, v).block_until_ready()
            warm_sweeps = tn.stats["sweeps"]
            warm_hits = tn.stats["store_hits"]

            # -- tuned pick vs the hand-set default -------------------
            _mark("autotune lane: tuned-vs-hand timing starting")
            tuned_ms = timed()  # store hit -> tuner-picked blocks
            hand_ms = timed(block_q=_DEFAULT_BLOCKS[0],
                            block_k=_DEFAULT_BLOCKS[1])
            tune.disable(save=False)

        row = {
            "autotune_cold_sweeps": cold_sweeps,
            "autotune_cold_trials": cold_trials,
            "autotune_warm_sweeps": warm_sweeps,
            "autotune_warm_store_hits": warm_hits,
            "autotune_flash_blocks": list(blocks),
            "autotune_flash_tuned_ms": round(tuned_ms, 3),
            "autotune_flash_hand_ms": round(hand_ms, 3),
            "autotune_flash_vs_hand": round(hand_ms / tuned_ms, 3)
            if tuned_ms else None,
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}
    finally:
        from nnstreamer_tpu import tune as _tn

        _tn.disable(save=False)


def _multiplex_lane(flops, device) -> dict:
    """N concurrent pipelines over ONE zoo bundle through one
    sched.DeviceEngine: the single dispatch loop coalesces same-shape
    head-of-line work across tenants into wide device batches, so the
    chip stops idling between per-pipeline frames. The serial
    utilization BENCH_r05 published (adaptive_batch16_pipeline_util =
    0.000965 — chip idle 99.9%) is the baseline this lane must beat;
    scripts/bench_compare.py aliases it for the cross-round delta."""
    import traceback

    try:
        from nnstreamer_tpu.graph import Pipeline
        from nnstreamer_tpu.sched import DeviceEngine
        from nnstreamer_tpu.utils import probes

        n_pipes = int(os.environ.get("BENCH_SCHED_PIPES", "8"))
        warm, frames = 8, 56
        eng = DeviceEngine("bench", autostart=True,
                           max_coalesce=max(n_pipes, 8))
        builts = []
        waits_ms = []
        try:
            for i in range(n_pipes):
                p = Pipeline(scheduler=eng)
                src = p.add_new("videotestsrc", width=SIZE, height=SIZE,
                                num_buffers=warm + frames,
                                pattern="random", seed=7 + i)
                conv = p.add_new("tensor_converter")
                filt = p.add_new("tensor_filter", framework="xla-tpu",
                                 model=MODEL)
                sink = p.add_new("tensor_sink")
                arrivals = []
                sink.new_data = (lambda buf, a=arrivals:
                                 a.append(time.monotonic()))
                Pipeline.link(src, conv, filt, sink)
                builts.append((p, arrivals))
            for p, _ in builts:
                p.start()
            for p, _ in builts:
                if not p.wait_eos(600):
                    raise TimeoutError("multiplex lane: EOS timeout")
            # per-tenant submit->dispatch waits, read BEFORE stop()
            # detaches the tenants
            waits_ms = [t.wait_stats()["median_s"] * 1e3
                        for t in eng.tenants() if t.wait_stats()["n"]]
        finally:
            for p, _ in builts:
                p.stop()
            cs = eng.coalesce_stats()
            occ = eng.occupancy()
            eng.stop()
        merged = sorted(t for _, a in builts for t in a)
        peak, med = _windowed_fps(merged, warm * n_pipes, 0,
                                  window=8 * n_pipes)
        if not np.isfinite(med):
            return {}
        row = {
            "multiplex_n_pipelines": n_pipes,
            "multiplex_fps": round(float(peak), 2),
            "multiplex_fps_median": round(float(med), 2),
            "multiplex_coalesce_width_median": round(cs["median"], 2),
            "multiplex_occupancy": round(occ, 4),
        }
        if waits_ms:
            row["multiplex_tenant_wait_median_ms"] = round(
                float(np.median(waits_ms)), 3)
        util = probes.pipeline_util(flops, med, device)
        if util is not None:
            row["multiplex_pipeline_util"] = round(util, 6)
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _multiplex_goodput_lane(device) -> dict:
    """Per-tenant goodput under an 8-tenant mix with one deadline-tight
    tenant: every tenant pushes the same device matmul through one
    sched.DeviceEngine while obs.slo attributes each batch, then the
    lane reports deadline-met work as a fraction of all work — overall
    and for the tight tenant alone. This is the *useful*-throughput
    counterpart to _multiplex_lane's occupancy story: a scheduler change
    that lifts coalesce width by starving the deadline tenant shows up
    here, not there."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp
        from nnstreamer_tpu.obs import slo as _slo
        from nnstreamer_tpu.sched import DeviceEngine

        n_tenants = int(os.environ.get("BENCH_SLO_TENANTS", "8"))
        rounds = 24
        dim = 256

        @jax.jit
        def _mm(x):
            return x @ x

        class _Filt:
            name = "goodput"

            def invoke(self, inputs):
                return [np.asarray(_mm(inputs[0]))]

        x = jnp.ones((dim, dim), jnp.float32)
        np.asarray(_mm(x))  # compile outside the measurement
        filt = _Filt()
        was_on = _slo.enabled()
        if not was_on:
            _slo.enable()
        eng = DeviceEngine("bench-slo", autostart=True,
                           max_coalesce=max(n_tenants, 8))
        try:
            tight_name = "tight0"
            tenants = [eng.register(tight_name, weight=1.0,
                                    deadline_ms=25.0)]
            tenants += [eng.register(f"bulk{i}", weight=1.0)
                        for i in range(1, n_tenants)]
            for _ in range(rounds):
                futs = [t.submit(filt, [x]) for t in tenants]
                for f in futs:
                    f.result(timeout=60)
            snap = _slo.snapshot()
        finally:
            eng.stop()
            if not was_on:
                _slo.disable()
        met = missed = shed = t_met = t_all = 0
        for name, row in snap["tenants"].items():
            out = row["outcomes"]
            met += out["met"]
            missed += out["missed"]
            shed += out["shed"]
            if name == tight_name:
                t_met = out["met"]
                t_all = out["met"] + out["missed"] + out["shed"]
        total = met + missed + shed
        if not total or not t_all:
            return {}
        row = {
            "multiplex_goodput_ratio": round(met / total, 4),
            "multiplex_goodput_tight_ratio": round(t_met / t_all, 4),
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _batched_point(labels_path: str, batch: int, quant: str = "",
                   n_batches: int = 24, warm: int = 4) -> tuple:
    """(fps, fps_median) for frames-per-tensor serving at ``batch`` —
    counts source frames. The source is an appsrc cycling pre-generated
    frames: at batch>=64 the equivalent frame rate passes 1 kFPS and a
    generate-per-frame videotestsrc would become the bottleneck being
    measured."""
    from nnstreamer_tpu.graph import Pipeline

    rng = np.random.default_rng(1)
    pool = [rng.integers(0, 255, (SIZE, SIZE, 3)).astype(np.uint8)
            for _ in range(8)]
    total = (n_batches + warm) * batch
    # shallow decode depth: one H2D per BATCH already amortizes transfer,
    # and the EOS-drain tail exclusion in _windowed_fps removes `depth`
    # arrivals — a deep pipeline would swallow the whole short run
    depth = 4
    p = Pipeline()
    src = p.add_new("appsrc", caps=_video_caps(),
                    data=(pool[i % len(pool)] for i in range(total)))
    conv = p.add_new("tensor_converter", frames_per_tensor=batch)
    filt = p.add_new("tensor_filter", framework="xla-tpu",
                     model=_with_batch(MODEL, batch),
                     custom=f"quant={quant}" if quant else "")
    dec = p.add_new("tensor_decoder", mode="image_labeling",
                    option1=labels_path, async_depth=depth)
    sink = p.add_new("tensor_sink")
    arrivals = []
    sink.new_data = lambda buf: arrivals.append(time.monotonic())
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=600)
    peak, med = _windowed_fps(arrivals, warm, depth, window=8)
    return peak * batch, med * batch


def _batch_sweep(labels_path: str, flops, device) -> dict:
    """Sweep the batch axis to (or past) the compute-bound
    knee; report FPS + MFU per point and a w8-quant point at the largest
    batch. Keys batch8_* keep round-over-round continuity."""
    import traceback

    from nnstreamer_tpu.utils import probes

    out: dict = {}
    sweep: dict = {}
    # 4 points span the curve; each batch size is its own XLA compile,
    # so resolution trades against the watchdog budget
    for batch in (8, 32, 64, 128):
        try:
            _mark(f"batch sweep b={batch} starting")
            peak, med = _batched_point(labels_path, batch, n_batches=16)
            if not np.isfinite(med):
                continue
            point = {"fps": round(peak, 2), "fps_median": round(med, 2)}
            if flops:
                point["mfu"] = round(
                    probes.mfu(flops, med, device) or 0.0, 6)
            # record the measured point BEFORE the split probe: the probe
            # is a second full-model compile, and a wedge there must not
            # cost the watchdog flush an existing number
            sweep[str(batch)] = point
            _partial.update({"batch_sweep": sweep})
            if batch in (8, 128):
                # split only at the curve's ends; watchdog budget is fixed
                _mark(f"batch sweep split probe b={batch}")
                split = _config_split(_with_batch(MODEL, batch), SIZE,
                                      batch=batch, k=8, device=device)
                if split:
                    point["split"] = split
            if batch == 8:
                out["batch8_fps"] = point["fps"]
                out["batch8_fps_median"] = point["fps_median"]
                if "mfu" in point:
                    out["batch8_mfu"] = point["mfu"]
        except Exception:
            traceback.print_exc(file=sys.stderr)
    try:
        _mark("batch sweep w8 quant point starting")
        peak, med = _batched_point(labels_path, 64, quant="w8",
                                   n_batches=16)
        if np.isfinite(med):
            point = {"fps": round(peak, 2), "fps_median": round(med, 2)}
            if flops:
                point["mfu"] = round(probes.mfu(flops, med, device) or 0.0,
                                     6)
            sweep["64_w8"] = point
    except Exception:
        traceback.print_exc(file=sys.stderr)
    if sweep:
        out["batch_sweep"] = sweep
    _partial.update(out)
    return out


def _transformer_bench() -> dict:
    """A transformer tokens/sec + MFU row. Causal-LM prefill scoring as
    a real pipeline (appsrc token batches → tensor_filter → sink
    materializing results), timed by wall-clock arrivals at the sink —
    no device-timer microbenchmarks. bf16 params + default
    TPU matmul precision (the production serving configuration; the
    exactness-pinned f32 zoo path stays as is). Output is last-token
    logits only so D2H stays small."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.core import Caps
        from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
        from nnstreamer_tpu.graph import Pipeline
        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.models.zoo import ModelBundle
        from nnstreamer_tpu.utils import probes

        V, D, H, L = _LM_DIMS
        B, T = int(os.environ.get("BENCH_LM_BATCH", "8")), \
            int(os.environ.get("BENCH_LM_SEQ", "1024"))
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, T)
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)

        # both lanes share _lm_prefill (bf16 default precision, last-token
        # unembed) so the ONLY difference between them is the attention
        # path — dense masked softmax vs the blockwise pallas kernel
        def score(p, tokens):
            logits, _, _, _ = causal_lm._lm_prefill(
                p, tokens.astype(jnp.int32), H, T, flash=False)
            return logits.astype(jnp.float32)

        def score_flash(p, tokens):
            logits, _, _, _ = causal_lm._lm_prefill(
                p, tokens.astype(jnp.int32), H, T, flash=True)
            return logits.astype(jnp.float32)

        n, warm = 24, 4
        rng = np.random.default_rng(0)
        toks = [rng.integers(0, V, (B, T)).astype(np.int32)
                for _ in range(4)]
        device = jax.devices()[0]

        def run_lane(fn, tag):
            bundle = ModelBundle(
                f"lm_prefill_bench{tag}", fn, params=params,
                in_info=TensorsInfo.from_strings(f"{T}:{B}", "int32"),
                out_info=TensorsInfo.from_strings(f"{V}:{B}", "float32"))
            p = Pipeline(f"bench-lm{tag}")
            caps = Caps.tensors(TensorsConfig(
                TensorsInfo.from_strings(f"{T}:{B}", "int32")))
            src = p.add_new("appsrc", caps=caps,
                            data=(toks[i % 4] for i in range(n + warm)))
            filt = p.add_new("tensor_filter", framework="xla-tpu",
                             model=bundle)
            sink = p.add_new("tensor_sink")
            arrivals: list = []

            def on_data(buf):
                buf.memories[0].host()  # materialize: honest wall-clock
                arrivals.append(time.monotonic())

            sink.new_data = on_data
            Pipeline.link(src, filt, sink)
            p.run(timeout=600)
            if len(arrivals) < warm + 8:
                return {}
            peak, med = _windowed_fps(arrivals, warm, 0, window=8)
            if not np.isfinite(med):
                return {}
            # analytic count: XLA cost_analysis counts the layer-scan
            # body once (~L x undercount, tests/test_flops_accounting.py)
            # and reports 0 for pallas custom calls — both lanes share
            # the closed form (identical math either way)
            flops = causal_lm.prefill_flops(B, T, D, L, V)
            row = {
                f"transformer_prefill{tag}_tokens_per_s":
                    round(peak * B * T, 1),
                f"transformer_prefill{tag}_tokens_per_s_median":
                    round(med * B * T, 1),
            }
            if flops:
                row[f"transformer_prefill{tag}_mfu"] = round(
                    probes.mfu(flops, med, device) or 0.0, 6)
                if not tag:
                    row["transformer_gflops_per_prefill"] = \
                        round(flops / 1e9, 1)
                    row["transformer_flops_accounting"] = (
                        "analytic closed form (models/causal_lm."
                        "prefill_flops); XLA cost_analysis undercounts "
                        "lax.scan bodies ~Lx, so pre-r5 artifacts "
                        "understate transformer MFU ~8x")
            return row

        row = run_lane(score, "")
        row["transformer_prefill_config"] = \
            f"d{D} L{L} h{H} V{V} batch{B} seq{T} bf16"
        _partial.update(row)
        if os.environ.get("BENCH_LM_FLASH", "1") != "0":
            _mark("transformer flash-prefill lane starting")
            row.update(run_lane(score_flash, "_flash"))
            _partial.update(row)
        if os.environ.get("BENCH_LM_DECODE", "1") != "0":
            _mark("transformer decode lane starting")
            row.update(_decode_lane(params, H, T, device))
            _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _timed(fn, *args, reps: int = 6) -> float:
    """Compile+warm once, then median wall-clock of ``reps`` host-
    materialized invokes (shared by the direct-jit lanes: decode,
    long-context)."""
    np.asarray(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        np.asarray(fn(*args))
        ts.append(time.monotonic() - t0)
    return float(np.median(ts))


def _decode_lane(params, n_heads, max_len, device) -> dict:
    """Autoregressive decode tokens/sec: greedy generation through the
    streaming KV cache. The whole generate loop (prefill a 128-token
    prompt, then ``lax.scan`` 64 decode steps feeding argmax back) runs
    as ONE compiled program, so the measurement is device decode
    throughput, not per-token dispatch latency; wall-clock is taken at host
    materialization of the generated tokens. This is the serving-side
    complement to the prefill lanes — memory-bandwidth-bound (one cache
    read per step) where prefill is MXU-bound."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models import causal_lm

        B, P, G = 8, 128, 64
        if P + G > max_len:
            # decode past cache capacity NaN-poisons logits by contract;
            # argmax would swallow that into token 0 and publish a
            # garbage rate — shrink to fit instead
            P = max(1, max_len // 2)
            G = max_len - P
            if G < 8:
                _mark(f"decode lane dropped: max_len={max_len} too small")
                return {}
        rng = np.random.default_rng(2)
        V = params["embed"].shape[0]
        prompt = jnp.asarray(
            rng.integers(0, V, (B, P)).astype(np.int32))

        @jax.jit
        def generate(p, prompt):
            # flash pinned off so the prefill share measures the same
            # program as prefill_only regardless of ambient NNS_LM_FLASH
            logits, kc, vc, pos = causal_lm._lm_prefill(
                p, prompt, n_heads, max_len, flash=False)
            first = jnp.argmax(
                logits, -1)[:, None].astype(jnp.int32)

            def step(carry, _):
                tok, kc, vc, pos = carry
                lg, kc, vc, pos = causal_lm._lm_decode_step(
                    p, tok, kc, vc, pos, n_heads)
                nxt = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
                return (nxt, kc, vc, pos), nxt[:, 0]

            (_, _, _, _), toks = jax.lax.scan(
                step, (first, kc, vc, pos), None, length=G)
            return toks.T  # (B, G)

        @jax.jit
        def prefill_only(p, prompt):
            logits, _, _, _ = causal_lm._lm_prefill(
                p, prompt, n_heads, max_len, flash=False)
            return jnp.argmax(logits, -1)

        with jax.default_matmul_precision("bfloat16"):
            med = _timed(generate, params, prompt)
            med_prefill = _timed(prefill_only, params, prompt)
        # steady-state decode rate: subtract the separately measured
        # prefill share so the row isn't dominated by the prompt matmul
        decode_s = med - med_prefill
        if decode_s <= 0:
            # 6-sample medians can cross; a clamped
            # subtraction would publish a garbage tokens/sec row
            _mark("decode lane dropped: prefill share >= total "
                  f"({med_prefill:.4f}s >= {med:.4f}s)")
            return {}
        row = {
            "transformer_decode_tokens_per_s":
                round(B * G / decode_s, 1),
            "transformer_decode_config":
                f"batch{B} prompt{P} gen{G} greedy kv-cache bf16",
            "transformer_decode_wall_s_median": round(med, 4),
            "transformer_decode_prefill_share_s": round(med_prefill, 4),
        }
        from nnstreamer_tpu.utils import probes

        # analytic decode FLOPs (causal_lm.decode_flops — cost_analysis
        # undercounts the scan-of-scan generate loop ~L*G x). Decode-only
        # MFU stays low by nature (bandwidth-bound); reported so the
        # prefill-vs-decode contrast is on the record
        D = params["embed"].shape[1]
        L = params["wqkv"].shape[0]
        dec_flops = causal_lm.decode_flops(B, P, G, D, L, V)
        mfu_val = probes.mfu(
            dec_flops / (B * G), B * G / decode_s, device)
        if mfu_val:
            row["transformer_decode_mfu"] = round(mfu_val, 6)

        if os.environ.get("BENCH_LM_W8A8", "1") != "0":
            # w8a8 point: decode is WEIGHT-bandwidth-bound (every step
            # re-reads the full stack), so int8 weights halve the bound
            # resource vs bf16; the same generate program retraces on
            # the quantized pytree through the shared matmul sites
            _mark("decode w8a8 point starting")
            qparams = jax.jit(causal_lm.quantize_lm_params)(params)
            med_q = _timed(generate, qparams, prompt)
            med_qp = _timed(prefill_only, qparams, prompt)
            dec_q = med_q - med_qp
            # raw medians ALWAYS published: the speedup is a difference
            # of two noisy medians divided by another — when a run is
            # noisy enough to drop the derived row, these make the lane
            # diagnosable instead of silently flaky
            row["transformer_decode_w8a8_wall_s_median"] = round(med_q, 4)
            row["transformer_decode_w8a8_prefill_share_s"] = \
                round(med_qp, 4)
            if dec_q > 0:
                row["transformer_decode_w8a8_tokens_per_s"] = \
                    round(B * G / dec_q, 1)
                row["transformer_decode_w8a8_speedup_vs_bf16"] = \
                    round(decode_s / dec_q, 3)
            else:
                _mark("decode w8a8 point dropped: prefill share >= total")
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _longctx_lane(device) -> dict:
    """Long-context prefill throughput: dense vs pallas-flash attention at
    T=4096 (B=2), plus the T=8192 (B=1) point where the dense score
    matrix cannot compile on this chip (round 5: 8.6 GB fails at
    compile) so flash is the only runnable path. All points process
    8192 tokens per step so rows are comparable to the main prefill
    lane. Direct-jit wall-clock like the decode lane; the D2H payload is
    the B last-token argmax ints, so the per-dispatch latency floor is
    common to every row."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.utils import probes

        V, D, H, L = _LM_DIMS
        points = [(4096, 2, (False, True)), (8192, 1, (True,))]
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_LONGCTX_FULL", "0") != "1":
            # dense T=4096 attention on host CPU takes minutes per step;
            # keep a tiny shape so validation runs still cover the lane
            points = [(256, 2, (False, True))]
        if os.environ.get("BENCH_LM_FLASH", "1") == "0":
            # same kill switch as the main prefill flash lane: a pallas
            # kernel that hangs the runtime can't be caught by try/except
            points = [(t, b, tuple(m for m in modes if not m))
                      for t, b, modes in points]
            points = [(t, b, m) for t, b, m in points if m]

        tokens_per_step = sorted({t * b for t, b, _ in points})
        row: dict = {
            "transformer_longctx_config":
                f"d{D} L{L} h{H} V{V} bf16; "
                f"{'/'.join(str(n) for n in tokens_per_step)} tokens/step",
        }
        rng = np.random.default_rng(3)
        for T, B, flash_modes in points:
            params = causal_lm.init_causal_lm(
                jax.random.PRNGKey(0), V, D, H, L, T)
            params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), params)
            toks = jnp.asarray(
                rng.integers(0, V, (B, T)).astype(np.int32))
            for flash in flash_modes:
                tag = "flash" if flash else "dense"
                _mark(f"longctx lane T={T} {tag} starting")
                try:
                    @jax.jit
                    def score(p, tokens, _flash=flash, _T=T):
                        logits, _, _, _ = causal_lm._lm_prefill(
                            p, tokens, H, _T, flash=_flash)
                        return jnp.argmax(logits, -1).astype(jnp.int32)

                    med = _timed(score, params, toks)
                    key = f"transformer_longctx_t{T}_{tag}"
                    row[f"{key}_tokens_per_s"] = round(B * T / med, 1)
                    # analytic closed form (causal_lm.prefill_flops):
                    # covers the flash points (pallas reports 0 flops to
                    # cost_analysis) and the dense points (the layer
                    # scan is undercounted ~Lx) alike
                    mfu_val = probes.mfu(
                        causal_lm.prefill_flops(B, T, D, L, V),
                        1.0 / med, device)
                    if mfu_val:
                        row[f"{key}_mfu"] = round(mfu_val, 6)
                except Exception:
                    # a failed point (OOM/compile) must not drop the
                    # points already measured — record and continue
                    traceback.print_exc(file=sys.stderr)
                    row[f"transformer_longctx_t{T}_{tag}_error"] = \
                        "point failed (see stderr)"
                _partial.update(row)
        if device.platform != "cpu":
            row["transformer_longctx_t8192_dense"] = (
                "skipped (expected OOM at compile on this chip class: "
                "8.6GB score matrix, round 5)")
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _prefill_knee_lane(device) -> dict:
    """Prefill batch knee: tokens/sec + MFU at batch 16/32/64 (T=1024,
    flash attention — the dense score matrix stops compiling past ~b32).

    At round 5 every dispatch paid a ~65 ms round-trip floor that the
    directly attached chip does not have, so the per-dispatch token
    count was the ONLY lever on measured utilization: at batch 8 the
    chip was idle ~95% of the wall clock. These points hold the model
    fixed and scale tokens per dispatch 2-8x, which bounds the
    framework-side overhead — if tokens/sec scales ~linearly with batch
    here, the low absolute MFU of the batch-8 rows is per-dispatch
    overhead, not the compiled program."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.utils import probes

        V, D, H, L = _LM_DIMS
        T, batches = 1024, (16, 32, 64)
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_KNEE_FULL", "0") != "1":
            V, D, H, L = 512, 64, 4, 2
            T, batches = 128, (16, 32)
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            causal_lm.init_causal_lm(jax.random.PRNGKey(0), V, D, H, L, T))
        use_flash = os.environ.get("BENCH_LM_FLASH", "1") != "0" \
            and device.platform != "cpu"

        @jax.jit
        def score(p, tokens):
            logits, _, _, _ = causal_lm._lm_prefill(
                p, tokens, H, T, flash=use_flash)
            # last-token argmax only: D2H stays B ints, so the row
            # measures prefill compute + H2D, not logits readback
            return jnp.argmax(logits, -1).astype(jnp.int32)

        row: dict = {"transformer_prefill_knee_config":
                     f"d{D} L{L} h{H} V{V} seq{T} bf16 "
                     f"{'flash' if use_flash else 'dense'}"}
        rng = np.random.default_rng(5)
        for B in batches:
            _mark(f"prefill knee batch {B} starting")
            key = f"transformer_prefill_b{B}"
            try:
                toks = jnp.asarray(
                    rng.integers(0, V, (B, T)).astype(np.int32))
                med = _timed(score, params, toks)
                row[f"{key}_tokens_per_s"] = round(B * T / med, 1)
                m = probes.mfu(causal_lm.prefill_flops(B, T, D, L, V),
                               1.0 / med, device)
                if m:
                    row[f"{key}_mfu"] = round(m, 6)
            except Exception:
                # one failed point (e.g. dense OOM past ~b32 when flash
                # is killed off) must not drop the measured points
                traceback.print_exc(file=sys.stderr)
                row[f"{key}_error"] = "point failed (see stderr)"
            _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _roofline_lane(device) -> dict:
    """MXU-roofline prefill: what the framework reaches when the model
    is actually MXU-shaped. The main lane's d1024 matmuls are small for
    a 128x128 systolic array (each layer's biggest GEMM tile is
    1024x4096 — utilization is capped by shape, not by the stack), so
    this lane runs a wide config — d4096, 32 heads of head_dim 128
    (exactly the TPU lane width), flash attention, bf16 — sized so one
    dispatch carries ~40 TFLOP and a per-dispatch latency floor (~65 ms
    at round 5) is a minor share (~20% at the then-measured 0.33 s
    step) instead of ~95%. The d1024 rows measure the small-model dispatch floor;
    this row measures the compiled-program ceiling on the same stack
    (same _lm_prefill code path, only the dims differ)."""
    import traceback

    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.utils import probes

        V, D, H, L = 8192, 4096, 32, 6
        B, T = 8, 2048
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_ROOFLINE_FULL", "0") != "1":
            V, D, H, L = 512, 256, 4, 2
            B, T = 4, 256
        use_flash = os.environ.get("BENCH_LM_FLASH", "1") != "0" \
            and device.platform != "cpu"

        # init+cast under one jit so each f32 leaf is freed after its
        # bf16 cast (the f32 tree alone is ~5 GB at these dims)
        @jax.jit
        def init(key):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16),
                causal_lm.init_causal_lm(key, V, D, H, L, T))

        params = init(jax.random.PRNGKey(0))

        @jax.jit
        def score(p, tokens):
            logits, _, _, _ = causal_lm._lm_prefill(
                p, tokens, H, T, flash=use_flash)
            # last-token argmax: D2H is B ints, same contract as the
            # other prefill lanes
            return jnp.argmax(logits, -1).astype(jnp.int32)

        rng = np.random.default_rng(7)
        toks = jnp.asarray(rng.integers(0, V, (B, T)).astype(np.int32))
        med = _timed(score, params, toks, reps=4)
        flops = causal_lm.prefill_flops(B, T, D, L, V)
        row = {
            "transformer_roofline_config":
                f"d{D} L{L} h{H} V{V} batch{B} seq{T} bf16 "
                f"{'flash' if use_flash else 'dense'}",
            "transformer_roofline_tokens_per_s": round(B * T / med, 1),
            "transformer_roofline_tflops_per_dispatch":
                round(flops / 1e12, 2),
            "transformer_roofline_step_s_median": round(med, 4),
        }
        m = probes.mfu(flops, 1.0 / med, device)
        if m:
            row["transformer_roofline_mfu"] = round(m, 6)
        _partial.update(row)

        if os.environ.get("BENCH_LM_W8A8", "1") != "0":
            # w8a8 point: same program shape, GEMMs on the MXU's int8
            # double-rate path (ops/int8.py; v5e 394 TOPS vs 197 TFLOP/s
            # bf16). score() retraces on the quantized pytree. The MFU
            # field keeps the bf16-peak basis so the speedup is visible
            # as a ratio; int8_util is the same time against the 2x peak
            _mark("roofline w8a8 point starting")
            qparams = jax.jit(causal_lm.quantize_lm_params)(params)
            med_q = _timed(score, qparams, toks, reps=4)
            row["transformer_roofline_w8a8_tokens_per_s"] = \
                round(B * T / med_q, 1)
            row["transformer_roofline_w8a8_step_s_median"] = round(med_q, 4)
            row["transformer_roofline_w8a8_speedup_vs_bf16"] = \
                round(med / med_q, 3)
            mq = probes.mfu(flops, 1.0 / med_q, device)
            if mq:
                row["transformer_roofline_w8a8_mfu_bf16_basis"] = \
                    round(mq, 6)
                row["transformer_roofline_w8a8_int8_util"] = \
                    round(mq / 2.0, 6)
            _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _serving_lane(device) -> dict:
    """Continuous-batching LM serving (serving/lm_engine.py) vs the
    static-batch baseline: the same mixed workload — varied prompt
    lengths and generation budgets — through the same engine twice,
    continuous admission vs gang (all-slots-free) admission. The row
    pair quantifies what iteration-level scheduling buys on this chip;
    results are greedy-exact in both modes (tests/test_lm_serving.py),
    so the delta is pure scheduling."""
    import traceback

    try:
        import jax

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.serving import LMEngine

        V, D, H, L = _LM_DIMS
        max_len, slots, chunk = 1024, 8, 16
        n_reqs, plens, gens = 24, (64, 192, 384, 512), (32, 64, 96, 128)
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_SERVING_FULL", "0") != "1":
            # full-size decode on host CPU is minutes; tiny validation shape
            V, D, H, L = 512, 64, 4, 2
            max_len, slots, chunk = 128, 4, 8
            n_reqs, plens, gens = 6, (8, 24), (8, 16)
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, max_len)

        rng = np.random.default_rng(5)
        reqs = [(rng.integers(0, V, plens[i % len(plens)])
                 .astype(np.int32), gens[i % len(gens)])
                for i in range(n_reqs)]

        def run_requests(request_list, **eng_kw):
            eng = LMEngine(params, H, max_len, n_slots=slots, **eng_kw)
            for p, g in request_list:
                eng.submit(np.ascontiguousarray(p), max_new=g)
            t0 = time.monotonic()
            res = eng.run()
            wall = time.monotonic() - t0
            toks = sum(len(v) for v in res.values())
            return toks / wall, eng.stats, wall, toks

        def run_mode(gang: bool):
            tps, stats, _, _ = run_requests(reqs, chunk=chunk, gang=gang)
            return tps, stats

        _mark("serving lane warmup (compiles) starting")
        run_mode(False)  # compile prefill buckets + chunk sizes once
        _mark("serving lane continuous starting")
        cont_tps, cont_stats = run_mode(False)
        _mark("serving lane static (gang) starting")
        gang_tps, gang_stats = run_mode(True)
        row = {
            "lm_serving_config":
                f"d{D} L{L} V{V} slots{slots} chunk{chunk} "
                f"reqs{n_reqs} prompts{min(plens)}-{max(plens)} "
                f"gen{min(gens)}-{max(gens)} greedy",
            "lm_serving_continuous_tokens_per_s": round(cont_tps, 1),
            "lm_serving_static_tokens_per_s": round(gang_tps, 1),
            "lm_serving_speedup": round(cont_tps / gang_tps, 3),
            "lm_serving_continuous_decode_steps":
                cont_stats["decode_steps"],
            "lm_serving_static_decode_steps": gang_stats["decode_steps"],
            # fraction of total slot capacity (slots x decode steps) that
            # produced no kept token — the utilization gap the scheduler
            # is fighting (engine invariant: capacity = kept + wasted)
            "lm_serving_continuous_waste_frac": round(
                cont_stats["wasted_slot_steps"]
                / max(1, slots * cont_stats["decode_steps"]), 3),
            "lm_serving_static_waste_frac": round(
                gang_stats["wasted_slot_steps"]
                / max(1, slots * gang_stats["decode_steps"]), 3),
        }
        _partial.update(row)

        # speculative decoding on a repetition-heavy workload (the
        # regime prompt-lookup targets — e.g. code/log continuation):
        # same requests through chunk=1 engines with and without drafts,
        # so the delta isolates accepted-draft tokens per dispatch
        _mark("serving lane speculative starting")
        base = rng.integers(0, V, 16).astype(np.int32)
        tiled = np.tile(base, -(-max(plens) // base.size))  # covers max
        rep_reqs = [(tiled[:plens[i % len(plens)]],
                     gens[i % len(gens)]) for i in range(n_reqs)]
        draft = 6
        # compile warmup: two short requests populate the same jit
        # caches (verify window (S, draft+1), chunk=1 step, prefill
        # buckets) as the full run at a fraction of the dispatches
        run_requests([(tiled[:p], 4) for p in plens],
                     chunk=1, spec_draft=draft)
        spec_tps, spec_stats, spec_wall, spec_toks = run_requests(
            rep_reqs, chunk=1, spec_draft=draft)
        plain_tps, plain_stats, plain_wall, _ = run_requests(
            rep_reqs, chunk=1)
        accept = spec_stats["spec_accepted"] \
            / max(1, spec_stats["spec_drafted"])
        # dispatch economics: a W-token verify costs more than a decode
        # step, so speculation wins iff tokens/dispatch growth beats the
        # per-dispatch cost growth — breakeven acceptance makes the
        # workload-dependence of the result a number, not a caveat.
        # Both runs pay the same prefill dispatches, so they sit in both
        # numerator walls AND both denominators (not counting them would
        # bias the ratio upward for the run with fewer dispatches)
        spec_per = spec_wall / max(1, spec_stats["spec_iterations"]
                                   + spec_stats["decode_steps"]
                                   + spec_stats["prefills"])
        plain_per = plain_wall / max(1, plain_stats["decode_steps"]
                                     + plain_stats["prefills"])
        cost_ratio = spec_per / plain_per
        row2 = {
            "lm_serving_spec_tokens_per_s": round(spec_tps, 1),
            "lm_serving_spec_off_tokens_per_s": round(plain_tps, 1),
            "lm_serving_spec_speedup": round(spec_tps / plain_tps, 3),
            "lm_serving_spec_accept_rate": round(accept, 3),
            "lm_serving_spec_tokens_per_dispatch": round(
                spec_toks / max(1, spec_stats["spec_iterations"]
                                + spec_stats["decode_steps"]
                                + spec_stats["prefills"]), 2),
            "lm_serving_spec_window_cost_ratio": round(cost_ratio, 2),
            "lm_serving_spec_breakeven_accept_rate": round(
                (cost_ratio - 1.0) / draft, 3),
            "lm_serving_spec_config":
                f"spec_draft={draft} chunk=1 greedy, period-16 "
                "repetitive prompts; a random-weight LM's own output "
                "barely repeats, so acceptance here is a FLOOR — "
                "speculation nets out when accept_rate exceeds the "
                "breakeven field (prompt-lookup's target workloads: "
                "code/log/doc continuation). For non-repetitive text "
                "through a high-RTT link, chunk>1 is the right tool "
                "(docs/performance.md token economics)",
        }
        row.update(row2)
        _partial.update(row2)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _serving_paged_lane(device) -> dict:
    """Paged KV cache (serving/kv_cache.py) vs contiguous slot caches on
    the SAME memory budget: the contiguous baseline runs slots_equiv
    slots (its cache is slots_equiv x max_len), the paged engine runs
    4x the slots on a page pool of exactly slots_equiv * max_len / ps
    pages. A shared-prefix workload (the regime radix sharing targets —
    e.g. a common system prompt) lets paging fit the extra concurrency:
    the prefix is resident once and every admission past the first is
    charged only its suffix. Greedy results are bit-identical to the
    contiguous engine (tests/test_kv_paging.py), so speedup here is
    pure admission concurrency, not numerics."""
    import traceback

    try:
        import jax

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.serving import LMEngine

        V, D, H, L = _LM_DIMS
        max_len, chunk, ps = 1024, 16, 64
        slots_equiv, paged_slots = 8, 32
        n_reqs, prefix_len = 64, 128
        plens, gens = (160, 192, 224, 256), (32, 64, 96, 128)
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_PAGED_FULL", "0") != "1":
            # full-size decode on host CPU is minutes; tiny validation shape
            V, D, H, L = 512, 64, 4, 2
            max_len, chunk, ps = 128, 8, 8
            slots_equiv, paged_slots = 4, 8
            n_reqs, prefix_len = 16, 32
            plens, gens = (40, 48, 56, 64), (8, 16)
        kv_pages = slots_equiv * max_len // ps  # 8-slot-equivalent pool
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, max_len)

        rng = np.random.default_rng(7)
        prefix = rng.integers(0, V, prefix_len).astype(np.int32)
        # two admission waves over the slot count; the second wave is
        # sorted longest-budget-first so slots freeing early (short
        # first-wave requests) pick up the long tail — complementary
        # pairing keeps every slot chain near-equal, so waste_frac
        # measures paging overhead, not workload raggedness (that is
        # the lm_serving lane's subject)
        wave = [gens[i % len(gens)] for i in range(n_reqs // 2)]
        budgets = wave + sorted(wave, reverse=True)
        reqs = []
        for i, g in enumerate(budgets):
            p = plens[i % len(plens)]
            suffix = rng.integers(0, V, p - prefix_len).astype(np.int32)
            reqs.append((np.concatenate([prefix, suffix]), g))

        def run_requests(n_slots, **eng_kw):
            eng = LMEngine(params, H, max_len, n_slots=n_slots,
                           chunk=chunk, **eng_kw)
            for p, g in reqs:
                eng.submit(np.ascontiguousarray(p), max_new=g)
            t0 = time.monotonic()
            res = eng.run()
            wall = time.monotonic() - t0
            toks = sum(len(v) for v in res.values())
            return toks / wall, res, eng

        _mark("paged serving lane warmup (compiles) starting")
        run_requests(paged_slots, kv_page_size=ps, kv_pages=kv_pages)
        run_requests(slots_equiv)
        _mark("paged serving lane paged run starting")
        paged_tps, paged_res, paged_eng = run_requests(
            paged_slots, kv_page_size=ps, kv_pages=kv_pages)
        _mark("paged serving lane contiguous baseline starting")
        base_tps, base_res, base_eng = run_requests(slots_equiv)
        kv = paged_eng.kv_stats
        pstats, bstats = paged_eng.stats, base_eng.stats
        row = {
            "lm_serving_paged_config":
                f"d{D} L{L} V{V} page{ps} pool{kv_pages} "
                f"slots{paged_slots} vs contiguous slots{slots_equiv} "
                f"(same KV bytes) chunk{chunk} reqs{n_reqs} "
                f"prefix{prefix_len} prompts{min(plens)}-{max(plens)} "
                f"gen{min(gens)}-{max(gens)} greedy",
            "lm_serving_paged_tokens_per_s": round(paged_tps, 1),
            "lm_serving_paged_baseline_tokens_per_s": round(base_tps, 1),
            "lm_serving_paged_speedup": round(paged_tps / base_tps, 3),
            # greedy paged == greedy contiguous is an invariant, not a
            # tolerance — a False here is a correctness regression
            "lm_serving_paged_exact": paged_res == base_res,
            "lm_serving_paged_waste_frac": round(
                pstats["wasted_slot_steps"]
                / max(1, paged_slots * pstats["decode_steps"]), 3),
            "lm_serving_paged_baseline_waste_frac": round(
                bstats["wasted_slot_steps"]
                / max(1, slots_equiv * bstats["decode_steps"]), 3),
            "lm_serving_paged_prefix_hit_rate": round(
                kv["hit_tokens"] / max(1, kv["prompt_tokens"]), 3),
            "lm_serving_paged_pages_peak": kv["pages_peak"],
            "lm_serving_paged_evictions": kv["evictions"],
            "lm_serving_paged_cow_copies": kv["cow_copies"],
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _disagg_serving_lane(device) -> dict:
    """Disaggregated prefill/decode serving (serving/disagg.py) vs the
    same engine unified, request-at-a-time on a shared-prefix workload:
    a role="prefill" worker runs chunked prefill and streams the
    finished KV pages to a role="decode" worker over one KV_PAGE_XFER
    frame; the decode worker splices + prefix-hits them. ``relative``
    is the cost of the split on ONE host (two engines + loopback wire
    round trips vs zero) — the split pays off when the fleets scale
    independently, so the gate is "the wire hop stays cheap", not "the
    split wins on localhost". Exactness is an invariant: the disagg
    tokens must equal the unified engine's bit-for-bit, and every
    shipped page must land (sent == received, zero re-prefills)."""
    import traceback

    try:
        import jax

        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.serving import LMEngine
        from nnstreamer_tpu.serving import disagg as _dsg

        V, D, H, L = _LM_DIMS
        max_len, chunk, ps = 512, 16, 32
        n_reqs, prefix_len, gen = 32, 128, 32
        plens = (160, 192, 224, 256)
        if device.platform == "cpu" and \
                os.environ.get("BENCH_LM_DISAGG_FULL", "0") != "1":
            V, D, H, L = 512, 64, 4, 2
            max_len, chunk, ps = 128, 8, 8
            n_reqs, prefix_len, gen = 12, 32, 12
            plens = (40, 48, 56, 64)
        kv_pages = 2 * max_len // ps  # 2-slot-equivalent pool per engine
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, max_len)

        rng = np.random.default_rng(7)
        prefix = rng.integers(0, V, prefix_len).astype(np.int32)
        reqs = []
        for i in range(n_reqs):
            p = plens[i % len(plens)]
            suffix = rng.integers(0, V, p - prefix_len).astype(np.int32)
            reqs.append(np.concatenate([prefix, suffix]))

        def mkeng(role=None):
            return LMEngine(params, H, max_len, n_slots=2, chunk=chunk,
                            kv_page_size=ps, kv_pages=kv_pages, role=role)

        def run_unified():
            eng = mkeng()
            outs, t0 = [], time.monotonic()
            for p in reqs:
                rid = eng.submit(np.ascontiguousarray(p), max_new=gen)
                eng.run()
                outs.append(eng.results[rid])
            wall = time.monotonic() - t0
            return sum(len(v) for v in outs) / wall, outs

        pre_eng, dec_eng = mkeng("prefill"), mkeng("decode")
        pre_w = _dsg.DisaggWorker(pre_eng)
        dec_w = _dsg.DisaggWorker(dec_eng)
        client = _dsg.DisaggClient([(pre_w.host, pre_w.port)],
                                   [(dec_w.host, dec_w.port)],
                                   page_size=ps)
        try:
            _mark("disagg serving lane warmup (compiles) starting")
            client.generate(reqs[0], gen)  # compiles both engines
            run_unified()
            _mark("disagg serving lane disagg run starting")
            outs, t0 = [], time.monotonic()
            for p in reqs:
                outs.append(client.generate(p, gen))
            disagg_wall = time.monotonic() - t0
            disagg_tps = sum(len(v) for v in outs) / disagg_wall
            _mark("disagg serving lane unified baseline starting")
            base_tps, base_outs = run_unified()
            row = {
                "disagg_serving_config":
                    f"d{D} L{L} V{V} page{ps} pool{kv_pages} "
                    f"prefill+decode workers over loopback wire vs "
                    f"unified, reqs{n_reqs} prefix{prefix_len} "
                    f"prompts{min(plens)}-{max(plens)} gen{gen} greedy",
                "disagg_serving_tokens_per_s": round(disagg_tps, 1),
                "disagg_serving_unified_tokens_per_s": round(base_tps, 1),
                "disagg_serving_relative": round(disagg_tps / base_tps, 3),
                # invariant, not a tolerance: False is a correctness bug
                "disagg_serving_exact": outs == base_outs,
                "disagg_serving_pages_sent": client.stats["pages_sent"],
                "disagg_serving_reprefills": client.stats["reprefills"],
                "disagg_serving_prefix_hit_rate": round(
                    dec_eng.prefix_hit_rate, 3),
                "disagg_serving_prefill_hit_rate": round(
                    pre_eng.prefix_hit_rate, 3),
            }
        finally:
            client.close()
            pre_w.stop()
            dec_w.stop()
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _fleet_lane(device) -> dict:
    """Fleet autoscaling (fleet/): halve a 4-worker unified-serving
    fleet mid-load via live session migration (fleet/migrate.py) and
    compare session goodput against the same load on the unhalved
    fleet. ``fleet_halved_goodput_ratio`` is the tentpole claim —
    streams survive a scale-in, so completed turns / offered turns
    holds at ~1.0 through two drains — and
    ``fleet_migration_seconds`` is the per-session bill (control round
    trip + KV-page ship + router re-pin, end to end)."""
    import traceback

    try:
        import jax

        from nnstreamer_tpu.fleet.migrate import LM_CAPS, SessionMigrator
        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.query.router import BackendSet, QueryRouter
        from nnstreamer_tpu.serving import LMEngine
        from nnstreamer_tpu.serving import disagg as _dsg

        V, D, H, L = 512, 64, 4, 2
        max_len, chunk, ps = 128, 8, 8
        n_workers, n_sessions, n_turns, gen = 4, 8, 4, 8
        if device.platform != "cpu" \
                and os.environ.get("BENCH_FLEET_FULL", "0") == "1":
            V, D, H, L = _LM_DIMS
            max_len, chunk, ps = 512, 16, 32
            n_sessions, gen = 16, 16
        kv_pages = 4 * max_len // ps
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, max_len)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, V, 3 * ps).astype(np.int32)
                   for _ in range(n_sessions)]

        def run(halve):
            engines = [LMEngine(params, H, max_len, n_slots=2,
                                chunk=chunk, kv_page_size=ps,
                                kv_pages=kv_pages)
                       for _ in range(n_workers)]
            workers = [_dsg.DisaggWorker(e) for e in engines]
            router = QueryRouter(
                BackendSet([(w.host, w.port) for w in workers],
                           "fleet-bench"), "fleet-bench")
            router.set_caps_provider(lambda: LM_CAPS)
            mig = SessionMigrator(router)
            ok, total, mig_secs = 0, 0, []
            t0 = time.monotonic()
            try:
                for turn in range(n_turns):
                    if halve and turn == n_turns // 2:
                        # the controller's scale-in path by hand, twice:
                        # deterministic victim, migrate census, drain
                        for _ in range(2):
                            active = [be for be in
                                      router.backends.backends()
                                      if be.state == "active"]
                            owned = router.backends.sessions_owned
                            victim = min(
                                active,
                                key=lambda be: (len(owned(be.endpoint)),
                                                be.endpoint))
                            for s in owned(victim.endpoint):
                                tgt = router.backends.pick(
                                    session=s,
                                    exclude=frozenset({victim.endpoint}))
                                if tgt is not None:
                                    r = mig.migrate(s, victim, tgt)
                                    mig_secs.append(r["seconds"])
                            router.remove_backend(victim.endpoint,
                                                  drain=True)
                    for i, prompt in enumerate(prompts):
                        total += 1
                        sid = f"bench-s{i}"
                        rmeta, _ = router.dispatch(
                            {"lm": {"prompt": [int(x) for x in prompt],
                                    "max_new": gen, "session": sid}},
                            b"", session=sid)
                        if rmeta.get("tokens"):
                            ok += 1
                wall = time.monotonic() - t0
            finally:
                router.close()
                for w in workers:
                    w.stop()
            return ok / max(1, total), wall, mig_secs, dict(mig.stats)

        _mark("fleet lane full run starting (compiles)")
        full_goodput, full_wall, _, _ = run(False)
        _mark("fleet lane halved run starting")
        halved_goodput, halved_wall, mig_secs, mstats = run(True)
        row = {
            "fleet_config":
                f"d{D} L{L} V{V} page{ps} {n_workers} unified workers "
                f"halved mid-load, {n_sessions} sessions x {n_turns} "
                f"turns gen{gen} greedy",
            "fleet_halved_goodput_ratio": round(
                halved_goodput / max(full_goodput, 1e-9), 3),
            "fleet_full_goodput": round(full_goodput, 3),
            "fleet_halved_goodput": round(halved_goodput, 3),
            "fleet_migration_seconds": round(
                sum(mig_secs) / max(1, len(mig_secs)), 4),
            "fleet_migrated_sessions": mstats["migrated"],
            "fleet_absorbed_sessions": mstats["absorbed"],
            "fleet_pages_moved": mstats["pages_moved"],
            "fleet_halved_wall_s": round(halved_wall, 2),
            "fleet_full_wall_s": round(full_wall, 2),
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _fleet_restore_lane(device) -> dict:
    """Crash restore (fleet/checkpoint.py): checkpoint a 3-worker
    fleet to neighbor shelves, SIGKILL-equivalent one worker
    (``DisaggWorker.kill()`` — no drain, no goodbye), and restore its
    sessions onto survivors. ``fleet_restore_seconds`` is the
    end-to-end bill (re-pin + checkpoint_send + page splice);
    ``fleet_restore_warm_ratio`` is what freshness buys — the fraction
    of post-restore prompt tokens served from restored prefix pages
    (re-prefill fallback would score ~0). The overhead sub-run prices
    the daemon itself: ``fleet_checkpoint_overhead_ratio`` is serving
    throughput with a checkpoint pass after every request over
    throughput without — gated at >= 0.95 in bench_compare."""
    import traceback

    try:
        import jax

        from nnstreamer_tpu.fleet import checkpoint as _ckpt
        from nnstreamer_tpu.fleet.migrate import LM_CAPS
        from nnstreamer_tpu.models import causal_lm
        from nnstreamer_tpu.query.router import BackendSet, QueryRouter
        from nnstreamer_tpu.serving import LMEngine
        from nnstreamer_tpu.serving import disagg as _dsg

        V, D, H, L = 512, 64, 4, 2
        max_len, chunk, ps = 128, 8, 8
        n_workers, n_sessions, gen = 3, 6, 8
        kv_pages = 4 * max_len // ps
        params = causal_lm.init_causal_lm(
            jax.random.PRNGKey(0), V, D, H, L, max_len)
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, V, 3 * ps).astype(np.int32)
                   for _ in range(n_sessions)]

        def mkeng():
            return LMEngine(params, H, max_len, n_slots=2, chunk=chunk,
                            kv_page_size=ps, kv_pages=kv_pages)

        engines = [mkeng() for _ in range(n_workers)]
        workers = [_dsg.DisaggWorker(e) for e in engines]
        router = QueryRouter(
            BackendSet([(w.host, w.port) for w in workers],
                       "restore-bench"), "restore-bench")
        router.set_caps_provider(lambda: LM_CAPS)
        daemons = []
        try:
            _mark("fleet restore lane first turns starting (compiles)")
            hist = {}
            for i, prompt in enumerate(prompts):
                sid = f"bench-r{i}"
                rmeta, _ = router.dispatch(
                    {"lm": {"prompt": [int(x) for x in prompt],
                            "max_new": gen, "session": sid}},
                    b"", session=sid)
                hist[sid] = [int(x) for x in prompt] + \
                    [int(t) for t in rmeta.get("tokens") or []]
            # checkpoint every engine to its neighbors' shelves — the
            # default deployment topology (NeighborStore over the
            # KV_PAGE_XFER wire)
            for i, w in enumerate(workers):
                peers = [workers[j].endpoint for j in range(n_workers)
                         if j != i]
                d = _ckpt.CheckpointDaemon(
                    engines[i], _ckpt.NeighborStore(peers),
                    lock=w._elock, name=f"bench-ckpt-{i}")
                d.run_once()
                daemons.append(d)
            # the busiest worker dies: ring placement varies with the
            # OS-assigned ports, and killing an idle worker would
            # leave nothing to restore
            vi = max(range(n_workers), key=lambda i: len(
                router.backends.sessions_owned(workers[i].endpoint)))
            victim = workers[vi]
            moved = router.backends.sessions_owned(victim.endpoint)
            _mark("fleet restore lane kill + restore starting")
            victim.kill()
            restorer = _ckpt.SessionRestorer(router)
            t0 = time.monotonic()
            report = restorer.restore_instance(
                victim.instance, victim.endpoint,
                daemons[vi].watermarks())
            restore_secs = time.monotonic() - t0
            # post-restore turn per moved session: warm ratio is the
            # prefix-hit fraction of the resent history, read off the
            # survivors' KV accounting
            live = [e for i, e in enumerate(engines) if i != vi]
            hit0 = sum(e._kv.stats["hit_tokens"] for e in live)
            tok0 = sum(e._kv.stats["prompt_tokens"] for e in live)
            for sid in moved:
                rmeta, _ = router.dispatch(
                    {"lm": {"prompt": hist[sid], "max_new": gen,
                            "session": sid}}, b"", session=sid)
                assert rmeta.get("tokens"), f"post-restore {sid} died"
            hits = sum(e._kv.stats["hit_tokens"] for e in live) - hit0
            toks = sum(e._kv.stats["prompt_tokens"] for e in live) - tok0
            warm = hits / max(1, toks)
        finally:
            router.close()
            for d in daemons:
                d.stop()
            for w in workers:
                w.stop()

        # daemon overhead: multi-turn serving with a synchronous
        # checkpoint pass every other turn-round vs none. Every pass
        # re-shelves all six advanced sessions, so this is still far
        # more frequent than the deployed shape (DEFAULT_INTERVAL_S
        # covers hundreds of turns); medians over interleaved reps
        # keep run-to-run scheduler noise out of the ratio
        def serve(checkpointed, ov_rounds=4):
            eng = mkeng()
            daemon = _ckpt.CheckpointDaemon(eng, _ckpt.MemoryStore(),
                                            name="bench-ov")
            ov_hist = {i: [int(x) for x in p]
                       for i, p in enumerate(prompts)}
            n_tok, t0 = 0, time.monotonic()
            for r in range(ov_rounds):
                for i in range(n_sessions):
                    rid = eng.submit(
                        np.asarray(ov_hist[i], np.int32), max_new=gen,
                        session=f"ov-{i}")
                    eng.run()
                    toks = [int(t) for t in eng.results[rid]]
                    ov_hist[i] += toks
                    n_tok += len(toks)
                if checkpointed and r % 2 == 1:
                    daemon.run_once()
            return n_tok / (time.monotonic() - t0)

        _mark("fleet restore lane overhead sub-run starting")
        serve(True)  # warm both paths (compiles, gather buckets)
        base_runs, ckpt_runs = [], []
        for _ in range(5):
            base_runs.append(serve(False))
            ckpt_runs.append(serve(True))
        base_tps = statistics.median(base_runs)
        ckpt_tps = statistics.median(ckpt_runs)
        row = {
            "fleet_restore_config":
                f"d{D} L{L} V{V} page{ps} {n_workers} unified workers, "
                f"{n_sessions} sessions gen{gen} greedy, kill worker 0 "
                f"after neighbor checkpoint, restore onto survivors",
            "fleet_restore_seconds": round(restore_secs, 4),
            "fleet_restore_warm_ratio": round(warm, 3),
            "fleet_checkpoint_overhead_ratio": round(
                ckpt_tps / max(base_tps, 1e-9), 3),
            "fleet_restored_sessions": report["restored"],
            "fleet_reprefilled_sessions": report["re_prefilled"],
            "fleet_restore_moved": len(moved),
        }
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _diag_lane(device) -> dict:
    """Incident diagnostics (obs/diag/): a traced multi-tenant sched
    run with the diag taps live, then the two costs that decide whether
    diag may stay on in production — ``diag_capture_seconds``, the wall
    cost of freezing one full debug bundle (evidence rings populated),
    and ``diag_critpath_coverage_ratio``, the fraction of root-span
    time the segment sweep attributes to a known segment rather than
    ``host_other`` (the attribution must explain the latency, not just
    conserve it)."""
    import tempfile
    import traceback

    try:
        from nnstreamer_tpu.core.buffer import TensorMemory
        from nnstreamer_tpu.obs import diag as _diag
        from nnstreamer_tpu.obs import tracing as _tracing
        from nnstreamer_tpu.sched import DeviceEngine

        class _Filt:
            def invoke(self, inputs):
                return [inputs[0].host() * 2]

            def invoke_coalesced(self, groups):
                return [[g[0].host() * 2] for g in groups]

        was_tracing = _tracing.enabled()
        _tracing.store().reset()
        _tracing.enable()
        with tempfile.TemporaryDirectory() as td:
            deng = _diag.enable(td)
            try:
                eng = DeviceEngine("bench-diag", autostart=False,
                                   max_coalesce=8)
                filt = _Filt()
                tenants = [eng.register(f"t{i}") for i in range(4)]
                coverages = []
                for req in range(24):
                    with _tracing.store().start_span(
                            "serving.request",
                            attrs={"tenant": f"t{req % 4}"}) as root:
                        futs = [t.submit(
                            filt,
                            [TensorMemory(np.ones((8, 8), np.float32))],
                            label="mm") for t in tenants]
                        while eng.pending():
                            eng.step()
                        for f in futs:
                            f.result(5.0)
                    res = _diag.analyze(
                        _tracing.store().spans_of(root.context.trace_id))
                    if res is not None:
                        assert (sum(res["segments"].values())
                                == res["total_ns"])
                        coverages.append(res["coverage_ratio"])
                cap_secs = []
                for i in range(5):
                    t0 = time.monotonic()
                    bid = deng.bundles.capture(
                        {"kind": "manual", "key": f"bench-{i}",
                         "detail": {}})
                    cap_secs.append(time.monotonic() - t0)
                    assert bid is not None
                row = {
                    "diag_config":
                        "4 tenants x 24 traced requests, coalesce<=8, "
                        "full-collector bundle x5",
                    "diag_capture_seconds": round(
                        float(np.median(cap_secs)), 4),
                    "diag_critpath_coverage_ratio": round(
                        float(np.median(coverages)), 4),
                    "diag_traces_analyzed": len(coverages),
                }
            finally:
                _diag.disable()
                (_tracing.enable if was_tracing else _tracing.disable)()
                _tracing.store().reset()
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _quality_lane(device) -> dict:
    """Data-plane quality (obs/quality/): the two costs that decide
    whether the layer may stay on in production —
    ``quality_overhead_ratio``, an instrumented pipeline's throughput
    over the uninstrumented run's (the <=5% overhead acceptance gate:
    the ratio must hold >= 0.95), and ``quality_drift_detect_seconds``,
    the wall time from the first frame of a shifted distribution to the
    both-windows PSI breach against a frozen baseline (short real
    windows — the lane proves the mechanism, not the 60s defaults)."""
    import tempfile
    import traceback

    try:
        from nnstreamer_tpu.core import Buffer
        from nnstreamer_tpu.graph import Pipeline
        from nnstreamer_tpu.obs import quality as _quality

        rng = np.random.default_rng(21)
        # the overhead gate is measured against the headline pipeline
        # SHAPE (video src -> converter -> mobilenet filter -> decoder
        # -> sink: every tap kind fires every frame) at a CPU-sized
        # input; the toy scaler pipelines elsewhere in this file move
        # bare buffers in ~100us/frame, which no per-frame statistics
        # layer can honestly undercut 20x
        q_size = int(os.environ.get("BENCH_QUALITY_SIZE", "96"))
        n_frames = int(os.environ.get("BENCH_QUALITY_FRAMES", "64"))
        labels_path = os.path.join(tempfile.mkdtemp(), "labels.txt")
        with open(labels_path, "w", encoding="utf-8") as fp:
            fp.write("\n".join(f"class{i}" for i in range(CLASSES)))

        def run_fps() -> float:
            p = Pipeline()
            src = p.add_new("videotestsrc", width=q_size, height=q_size,
                            num_buffers=n_frames, pattern="random")
            conv = p.add_new("tensor_converter")
            filt = p.add_new(
                "tensor_filter", framework="xla-tpu",
                model=f"zoo://mobilenet_v2?width=1.0&size={q_size}")
            dec = p.add_new("tensor_decoder", mode="image_labeling",
                            option1=labels_path, async_depth=8)
            sink = p.add_new("tensor_sink")
            Pipeline.link(src, conv, filt, dec, sink)
            t0 = time.monotonic()
            p.run(timeout=300)
            return n_frames / max(time.monotonic() - t0, 1e-9)

        _quality.disable()
        run_fps()  # warmup (compile, element registry, allocator)
        # interleaved off/on pairs, best-of each arm: a sequential
        # off-block then on-block puts any slow machine-load drift
        # entirely on one arm, and a single GC stall poisons a median
        # of three — pairing cancels the drift, max() the stalls
        off_runs, on_runs = [], []
        try:
            for _ in range(4):
                _quality.disable()
                off_runs.append(run_fps())
                _quality.enable()
                on_runs.append(run_fps())
        finally:
            _quality.disable()
        fps_off = float(max(off_runs))
        fps_on = float(max(on_runs))

        # drift detection: freeze a baseline on the reference
        # distribution, then feed a shifted stream until both windows
        # breach (frames keep arriving while the slow window fills, so
        # the reading is arrival-to-page wall time, not just window
        # length)
        fast_s, slow_s = 0.05, 0.25
        ref = rng.normal(1.0, 0.25, (64, 32, 32)).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            base_path = os.path.join(td, "baseline.json")
            eng = _quality.enable()
            try:
                for f in ref:
                    eng.observe_chain("cam0", Buffer.of(f))
                eng.save_baseline(base_path)
            finally:
                _quality.disable()
            eng = _quality.enable(baseline=base_path,
                                  fast_window_s=fast_s,
                                  slow_window_s=slow_s)
            try:
                # healthy traffic first: both windows must hold
                # on-baseline scores before the shift, so the reading
                # is switch-to-breach (old low scores have to age out
                # or be outvoted), not first-sample-into-empty-windows
                t0 = time.monotonic()
                i = 0
                while time.monotonic() - t0 < slow_s * 1.2:
                    eng.observe_chain("cam0", Buffer.of(ref[i % len(ref)]))
                    i += 1
                    time.sleep(0.005)
                shifted = (ref[0] * 512.0)  # nine octaves away
                detect_s = None
                t0 = time.monotonic()
                while time.monotonic() - t0 < 10.0:
                    eng.observe_chain("cam0", Buffer.of(shifted))
                    ev = eng.evaluate("chain:cam0")
                    if ev is not None and ev["drift"] is not None \
                            and ev["drift"]["breached"]:
                        detect_s = time.monotonic() - t0
                        break
                    time.sleep(0.005)
            finally:
                _quality.disable()
        row = {
            "quality_config": (
                f"{n_frames}-frame mobilenet_v2 size={q_size} headline "
                f"shape, best of 4 interleaved off/on pairs; drift "
                f"windows fast={fast_s}s slow={slow_s}s"),
            "quality_overhead_ratio": round(fps_on / fps_off, 4),
            "quality_fps_off": round(fps_off, 1),
            "quality_fps_on": round(fps_on, 1),
        }
        if detect_s is not None:
            row["quality_drift_detect_seconds"] = round(detect_s, 4)
        _partial.update(row)
        return row
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def _last_json_record(stdout: str, key: str):
    """Last stdout line that parses as JSON and carries ``key``."""
    for line in reversed(stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if key in rec:
            return rec
    return None


def _cpu_child_run(extra_env: dict) -> float:
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_CPU_CHILD="1",
               BENCH_FRAMES="144",
               BENCH_DEPTH="8",
               BENCH_EXTRAS="0",
               BENCH_REPEATS="1",
               **extra_env)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=600)
        rec = _last_json_record(out.stdout, "value")
        if rec is not None:
            return float(rec.get("fps_median") or rec["value"])
    except Exception:
        pass
    return float("nan")


_TFLITE_XNNPACK_PROBE = r"""
import json, os, sys, time
import numpy as np
try:
    import tensorflow as tf

    path = sys.argv[1]
    it = tf.lite.Interpreter(model_path=path,
                             num_threads=os.cpu_count() or 4)
    it.allocate_tensors()
    d = it.get_input_details()[0]
    rng = np.random.default_rng(0)
    frames = [rng.integers(0, 255, tuple(d["shape"]), dtype=np.uint8)
              for _ in range(8)]
    oi = it.get_output_details()[0]["index"]
    for i in range(16):  # warmup
        it.set_tensor(d["index"], frames[i % 8]); it.invoke()
    n = 120
    t0 = time.perf_counter()
    for i in range(n):
        it.set_tensor(d["index"], frames[i % 8])
        it.invoke()
        it.get_tensor(oi)
    print(json.dumps({"fps": n / (time.perf_counter() - t0)}))
except Exception as e:
    print(json.dumps({"error": str(e)[:200]}))
"""


def _tflite_interpreter_fps() -> Tuple[float, str]:
    """The REAL thing being replaced: the reference's own serving stack —
    mobilenet quant through tf.lite.Interpreter (all cores; delegate
    provenance captured from the interpreter's own log line). The honest
    CPU comparator the jax-CPU lanes can flatter against. Subprocess
    (TensorFlow on the CPU, no JAX): TF must not contaminate the
    parent's backends.
    Returns (fps, delegate-or-error note)."""
    model = ("/root/reference/tests/test_models/models/"
             "mobilenet_v2_1.0_224_quant.tflite")
    if not os.path.isfile(model):
        return float("nan"), "reference model not mounted"
    try:
        out = subprocess.run(
            [sys.executable, "-c", _TFLITE_XNNPACK_PROBE, model],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, BENCH_CPU_CHILD="0", JAX_PLATFORMS="cpu"))
        delegate = "xnnpack" if "XNNPACK delegate" in (
            out.stderr + out.stdout) else "default-kernels"
        rec = _last_json_record(out.stdout, "fps")
        if rec is not None:
            return float(rec["fps"]), delegate
        err = _last_json_record(out.stdout, "error")
        note = err["error"] if err else f"no fps in output (rc={out.returncode})"
    except Exception as e:
        note = f"{type(e).__name__}: {e}"
    _mark(f"tflite interpreter comparator failed: {note}")
    return float("nan"), note


def _cpu_reference() -> dict:
    """Strongest same-host CPU numbers : the per-frame
    pipeline AND batch-8 frames-per-tensor serving (XLA-CPU threads
    across cores; batching amortizes per-frame pipeline overhead the
    same way the reference's tflite+XNNPACK batch path would), PLUS the
    reference's actual serving stack — tf.lite.Interpreter with XNNPACK
    on the same model file. All run in subprocesses so backends don't
    collide; vs_baseline uses the best of the three."""
    plain = _cpu_child_run({})
    batched = _cpu_child_run({"BENCH_CPU_BATCH": "8"})
    tflite_fps, tflite_note = _tflite_interpreter_fps()
    out = {}
    if np.isfinite(plain):
        out["cpu_reference_fps"] = round(plain, 2)
    if np.isfinite(batched):
        out["cpu_reference_batch8_fps"] = round(batched, 2)
    if np.isfinite(tflite_fps):
        out["cpu_reference_tflite_fps"] = round(tflite_fps, 2)
        out["cpu_reference_tflite_delegate"] = tflite_note
    else:
        # the lane this comparator exists for must not vanish silently
        out["cpu_reference_tflite_error"] = tflite_note
    candidates = [v for v in (plain, batched, tflite_fps)
                  if np.isfinite(v) and v > 0]
    if candidates:
        out["cpu_reference_best_fps"] = round(max(candidates), 2)
    return out


def _mark(msg: str) -> None:
    import time as _t

    print(f"[bench +{_t.monotonic() - _T0:.0f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.monotonic()


def _sanitize(obj):
    """NaN/inf → None so the emitted line is strict JSON."""
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def main() -> None:
    _arm_watchdog()
    cpu_child = os.environ.get("BENCH_CPU_CHILD") == "1"
    import jax

    from nnstreamer_tpu.core.hw import enable_compile_cache
    from nnstreamer_tpu.utils import probes

    enable_compile_cache()
    if not cpu_child:
        # a device without a recorded peak (a CPU) is an error here, not
        # a fallback: every utilization below divides by this
        probes.chip_peak_flops(jax.devices()[0])
    n_warmup, n_frames = 16, int(os.environ.get("BENCH_FRAMES", "256"))
    rng = np.random.default_rng(0)
    frames = [rng.integers(0, 255, (SIZE, SIZE, 3)).astype(np.uint8)
              for _ in range(8)]

    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False) as f:
        f.write("\n".join(f"label{i}" for i in range(CLASSES)))
        labels_path = f.name

    cpu_batch = int(os.environ.get("BENCH_CPU_BATCH", "0"))
    if cpu_child and cpu_batch > 1:
        # batched-CPU child lane: one frames-per-tensor measurement, one
        # JSON line (the parent takes the strongest CPU number)
        peak, med = _batched_point(labels_path, cpu_batch, n_batches=12)
        print(json.dumps(_sanitize(
            {"value": round(peak, 2), "fps_median": round(med, 2)})))
        return

    _mark("latency run (sync) starting")
    # -- latency run (synchronous invokes, per-frame timing) ----------------- #
    lat_frames = [frames[i % len(frames)] for i in range(n_warmup + 64)]
    p, filt, _ = build_pipeline(lat_frames, labels_path, sync=True)
    lats = []
    orig_record = filt.stats.record
    filt.stats.record = lambda ns: (orig_record(ns), lats.append(ns))[0]
    p.run(timeout=600)
    p50_us = float(np.percentile(np.asarray(lats[n_warmup:]) / 1000.0, 50))

    # -- throughput runs (async dispatch, end-to-end pipeline FPS) ----------- #
    # >=3 repeats: round 5 saw 89-205 FPS run-to-run on identical code,
    # so cross-round deltas need median-of-medians plus the observed
    # spread, not a single shot
    n_repeats = max(1, int(os.environ.get("BENCH_REPEATS", "3")))
    peaks, medians, r2_peaks = [], [], []
    for rep in range(n_repeats):
        _mark(f"throughput run {rep + 1}/{n_repeats} starting")
        tp_frames = [frames[i % len(frames)]
                     for i in range(n_warmup + n_frames)]
        p2, filt2, sink2 = build_pipeline(tp_frames, labels_path,
                                          sync=False)
        arrivals = []
        sink2.new_data = lambda buf: arrivals.append(time.monotonic())
        p2.run(timeout=600)
        rep_peak, rep_med = _windowed_fps(arrivals, n_warmup, DECODE_DEPTH)
        # r1/r2 methodology for cross-round comparability: peak window
        # with the EOS drain burst INCLUDED (overstates steady state)
        rep_r2, _ = _windowed_fps(arrivals, n_warmup, 0)
        if np.isfinite(rep_med):
            peaks.append(rep_peak)
            medians.append(rep_med)
            r2_peaks.append(rep_r2)
        _partial["fps_median_runs"] = [round(m, 2) for m in medians]
    if not medians:
        peaks = medians = r2_peaks = [float("nan")]
    fps = float(np.max(peaks))
    fps_median = float(np.median(medians))
    fps_r2_method = float(np.max(r2_peaks))

    from nnstreamer_tpu.models.zoo import get_model

    device = jax.devices()[0]

    _mark("phase-split probes starting")
    # -- instrumentation: per-phase split + MFU ------------------------------ #
    split = flops = mfu_val = None
    try:
        bundle = get_model(MODEL)
        fn = bundle.fn()
        example = frames[0][None]
        split = probes.phase_split(fn, [example], device=device, k=32)
        flops = probes.model_flops(fn, example)
        if not cpu_child:  # the comparator child's device has no peak
            mfu_val = probes.mfu(flops, fps_median, device)
    except Exception:
        import traceback

        traceback.print_exc(file=sys.stderr)

    result = _partial
    result.update({
        "metric": f"mobilenet_v2_{SIZE}_pipeline_fps",
        "value": round(fps, 2),
        "unit": "frames/sec",
        "fps_median": round(fps_median, 2),
        "fps_median_runs": [round(m, 2) for m in medians],
        "fps_median_spread": [round(float(np.min(medians)), 2),
                              round(float(np.max(medians)), 2)],
        "fps_peak_r2_method": round(fps_r2_method, 2),
        "p50_invoke_us": round(p50_us, 1),
        "frames": n_frames,
        "repeats": n_repeats,
        "device": str(device),
    })
    if split is not None:
        result["split"] = split
    if flops:
        result["model_gflops"] = round(flops / 1e9, 3)
    if mfu_val is not None:
        result["mfu"] = round(mfu_val, 6)

    if not cpu_child and os.environ.get("BENCH_CPU_REF", "1") != "0":
        _mark("same-host CPU reference starting")
        cpu = _cpu_reference()
        result.update(cpu)
        best = cpu.get("cpu_reference_best_fps")
        if best:
            result["vs_baseline"] = round(fps_median / best, 3)
            # name the lane that actually won so the comparator's
            # provenance is in the record, not just its number
            if best == cpu.get("cpu_reference_tflite_fps"):
                result["vs_baseline_kind"] = (
                    "speedup_vs_tflite_interpreter_same_host_"
                    + cpu.get("cpu_reference_tflite_delegate", "unknown"))
            else:
                result["vs_baseline_kind"] = \
                    "speedup_vs_strongest_same_host_jax_cpu"
    if "vs_baseline" not in result:
        # fallback: the 30 FPS real-time camera rate the reference
        # pipelines are built around
        result["vs_baseline"] = round(fps_median / 30.0, 3)
        result["vs_baseline_kind"] = "fps_median_over_30fps_realtime"

    if os.environ.get("BENCH_EXTRAS", "1") != "0":
        try:
            import tempfile as _tf

            with _tf.TemporaryDirectory() as td:
                result.update(_extra_benches(td))
            _mark("batch sweep starting")
            result.update(_batch_sweep(labels_path, flops, device))
            _mark("adaptive batch bench starting")
            result.update(_adaptive_bench(labels_path))
            if os.environ.get("BENCH_EPILOGUE_FUSION", "1") != "0":
                _mark("epilogue fusion lane starting")
                result.update(_epilogue_fusion_lane(device))
            if os.environ.get("BENCH_AUTOTUNE", "1") != "0":
                _mark("autotune lane starting")
                result.update(_autotune_lane(device))
            _mark("transformer prefill bench starting")
            result.update(_transformer_bench())
            if os.environ.get("BENCH_LM_LONGCTX", "1") != "0":
                _mark("long-context prefill lane starting")
                result.update(_longctx_lane(device))
            if os.environ.get("BENCH_LM_KNEE", "1") != "0":
                _mark("prefill batch-knee lane starting")
                result.update(_prefill_knee_lane(device))
            if os.environ.get("BENCH_LM_ROOFLINE", "1") != "0":
                _mark("MXU roofline lane starting")
                result.update(_roofline_lane(device))
            if os.environ.get("BENCH_LM_SERVING", "1") != "0":
                _mark("continuous-batching serving lane starting")
                result.update(_serving_lane(device))
            if os.environ.get("BENCH_LM_PAGED", "1") != "0":
                _mark("paged-KV serving lane starting")
                result.update(_serving_paged_lane(device))
            if os.environ.get("BENCH_LM_DISAGG", "1") != "0":
                _mark("disaggregated serving lane starting")
                result.update(_disagg_serving_lane(device))
            if os.environ.get("BENCH_FLEET", "1") != "0":
                _mark("fleet autoscale lane starting")
                result.update(_fleet_lane(device))
            if os.environ.get("BENCH_FLEET_RESTORE", "1") != "0":
                _mark("fleet checkpoint/restore lane starting")
                result.update(_fleet_restore_lane(device))
            if os.environ.get("BENCH_DIAG", "1") != "0":
                _mark("diag capture/critpath lane starting")
                result.update(_diag_lane(device))
            if os.environ.get("BENCH_QUALITY", "1") != "0":
                _mark("quality overhead/drift lane starting")
                result.update(_quality_lane(device))
            _mark("composite LSTM+query bench starting")
            result.update(_composite_bench())
            if os.environ.get("BENCH_SCHED_MULTIPLEX", "1") != "0":
                _mark("multi-tenant multiplex lane starting")
                result.update(_multiplex_lane(flops, device))
            if os.environ.get("BENCH_SCHED_GOODPUT", "1") != "0":
                _mark("multi-tenant goodput lane starting")
                result.update(_multiplex_goodput_lane(device))
            if flops and result.get("adaptive_batch16_fps_median"):
                # honest label: end-to-end pipeline rate × per-frame
                # FLOPs over peak is *pipeline utilization* (the chip is
                # idle between the 200ms batching budgets), not MFU —
                # BENCH_r05 published 0.000965 under the old "_mfu" key
                result["adaptive_batch16_pipeline_util"] = round(
                    probes.pipeline_util(
                        flops, result["adaptive_batch16_fps_median"],
                        device) or 0.0, 6)
        except Exception:  # never lose the headline measurement
            import traceback

            traceback.print_exc(file=sys.stderr)
    print(json.dumps(_sanitize(result)))


if __name__ == "__main__":
    main()
