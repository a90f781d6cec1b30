#!/usr/bin/env python3
"""chip_smoke.py — prove on one TPU that the system's two main paths run.

    python chip_smoke.py          # from the checkout root, on a TPU host

One process, no children, no network, nothing read outside the checkout:
labels, box priors, frames, prompts and weights are generated from seeds.
It drives the stream path (``nns-launch`` pipeline string, ``graph.Pipeline``,
the four BASELINE.json pipelines with their decoders), every Pallas kernel at
the shape its production caller uses, and the serving path (``LMEngine``
contiguous / paged / w8a8, flash prefill), checks each result against a
reference, and — with four or more devices — serves the same work over a
mesh. Any failed check raises: there is no leg whose failure leaves exit
code 0. On a host where JAX finds no TPU it exits 2 before compiling
anything. The last line of stdout is one JSON object naming the device,
the line before it the summary (legs, compile seconds, cache hits).

Timing lines are set-up evidence (compile seconds apart from run seconds,
first call vs warm compile cache), not benchmark numbers.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import tempfile
import threading
import time
from fractions import Fraction

import numpy as np

import jax
import jax.numpy as jnp

from nnstreamer_tpu.core import hw

#: sizes of every leg, in one place. Widths are the full published widths
#: of the models the repo ships; only frame/request counts are small.
CFG = {
    "frames": 64,            # headline pipeline, CLI and graph.Pipeline
    "ref_frames": 8,         # of which compared with the CPU float32 model
    "aux_frames": 4,         # SSD / DeepLab / PoseNet
    # V, D, H, L: a head is 128 wide like the benchmark's model, so that
    # the serving legs' decode steps take pallas.decode_attention (a head
    # of 64 takes the dense form)
    "lm_dims": (8192, 1024, 8, 8),
    "lm_max_len": 1024,
    "lm_slots": 8,
    "lm_prompts": (64, 96, 128, 200, 384, 512),
    "lm_prefix": 64,         # tokens every prompt shares (4 pages of 16)
    "lm_new": 32,
    "flash": ((8, 32, 2048, 128), (1, 16, 8192, 64)),
    "dgr_f": (4 * 1024, 4 * 4096),
    "dgr_rows": (8, 2048),
    # the benchmark's decode step: slots, heads, max_len, head size; two
    # layers of the store stand for its 24
    "decode_attn": (8, 16, 2048, 128),
    "decode_attn_layers": 2,
}


# --------------------------------------------------------------------------- #
# bookkeeping: legs, compile seconds, Mosaic evidence
# --------------------------------------------------------------------------- #

class Clock:
    """Wall time per leg, split into compile seconds — JAX's own
    backend-compile events: the XLA compile, or its load from the
    persistent cache — and everything else (tracing and lowering included,
    which no cache saves)."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.legs = []
        self._lock = threading.Lock()  # pipeline threads compile too
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def leg(self, name: str, fn, *args):
        c0, t0 = self.compile_s, time.monotonic()
        detail = fn(*args)
        wall = time.monotonic() - t0
        comp = self.compile_s - c0
        row = {"leg": name, "compile_s": round(comp, 2),
               "run_s": round(max(wall - comp, 0.0), 2), **(detail or {})}
        self.legs.append(row)
        print(f"[leg] {json.dumps(row)}", flush=True)
        return row


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def on_tpu_array(x) -> bool:
    return all(hw.on_tpu(d) for d in x.devices())


def mosaic_program(fn, *args):
    """Compile ``fn`` for the device its arguments live on and prove the
    Mosaic kernel — not the jnp reference — is what that program calls."""
    lowered = jax.jit(fn).lower(*args)
    require("tpu_custom_call" in lowered.as_text(),
            f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
            "lowered program (the Pallas kernel was not taken)")
    return lowered.compile()


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# --------------------------------------------------------------------------- #
# leg: gate
# --------------------------------------------------------------------------- #

def versions() -> dict:
    from importlib import metadata

    return {pkg: metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")}


def gate_leg() -> dict:
    from nnstreamer_tpu.utils import native, probes

    dev = jax.devices()[0]
    peak = probes.chip_peak_flops(dev)  # raises: kind not in the table
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices()), "peak_bf16_flops": peak,
            "peak_hbm_bytes_s": probes.chip_peak_hbm_bw(dev),
            "native_runtime": "loaded" if native.get_lib() is not None
            else "numpy path", **versions()}


# --------------------------------------------------------------------------- #
# leg: stream main path — MobileNet-v2 1.0 / 224 / 1001
# --------------------------------------------------------------------------- #

_NORMALIZE = "typecast:float32,add:-127.5,div:127.5"
_MNV2 = "zoo://mobilenet_v2?width=1.0&size=224&num_classes=1001"


def _video_caps(size: int):
    from nnstreamer_tpu.core import Caps

    return Caps("video/x-raw", {"format": "RGB", "width": size,
                                "height": size, "framerate": Fraction(0, 1)})


def _frames(n: int, size: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (size, size, 3)).astype(np.uint8)
            for _ in range(n)]


def cli_leg(tmp: str) -> dict:
    """The README's pipeline string through the entry point ``nns-launch``
    calls."""
    from nnstreamer_tpu import cli

    labels = os.path.join(tmp, "labels1001.txt")
    with open(labels, "w", encoding="utf-8") as f:
        f.write("\n".join(f"label{i}" for i in range(1001)))
    n = CFG["frames"]
    rc = cli.main([
        f"videotestsrc num-buffers={n} width=224 height=224 pattern=random "
        f"! tensor_converter "
        f"! tensor_transform mode=arithmetic option={_NORMALIZE} "
        f"! tensor_filter framework=xla-tpu model={_MNV2} "
        f"! tensor_decoder mode=image_labeling option1={labels} "
        f"! tensor_sink"])
    require(rc == 0, f"nns-launch returned {rc}")
    return {"frames": n, "rc": rc}


def pipeline_leg() -> dict:
    """Same chain through ``graph.Pipeline`` with a storing sink after the
    filter; logits against a float32 CPU evaluation of the same params."""
    from nnstreamer_tpu.graph import Pipeline
    from nnstreamer_tpu.models.mobilenet_v2 import MobileNetV2
    from nnstreamer_tpu.models.zoo import get_model

    n, n_ref = CFG["frames"], CFG["ref_frames"]
    frames = _frames(n, 224, seed=1)
    p = Pipeline("smoke")
    src = p.add_new("appsrc", caps=_video_caps(224), data=frames)
    conv = p.add_new("tensor_converter")
    norm = p.add_new("tensor_transform", mode="arithmetic",
                     option=_NORMALIZE)
    filt = p.add_new("tensor_filter", framework="xla-tpu", model=_MNV2)
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, norm, filt, sink)
    p.start()  # run() by hand: the filter's backend is gone after stop()
    try:
        require(p.wait_eos(600), "pipeline did not reach EOS")
        require(p.bus.error is None, f"pipeline error: {p.bus.error}")
        device = filt.fw._device
    finally:
        p.stop()
    require(sink.num_buffers == n, f"{sink.num_buffers}/{n} frames arrived")
    require(hw.on_tpu(device), f"filter placed on {device}")
    outs = [b.memories[0] for b in sink.buffers]
    require(all(m.is_device and on_tpu_array(m.device()) for m in outs),
            "filter outputs left the TPU")
    got = np.stack([m.host().reshape(-1) for m in outs])
    require(got.shape == (n, 1001) and np.isfinite(got).all(),
            f"logits {got.shape}, finite={np.isfinite(got).all()}")

    # float32 evaluation of the SAME params on the in-process CPU backend
    bundle = get_model(_MNV2)
    cpu = jax.devices("cpu")[0]
    model32 = MobileNetV2(num_classes=1001, width=1.0, dtype=jnp.float32)
    params_cpu = jax.device_put(bundle.params, cpu)
    ref_fn = jax.jit(lambda x: model32.apply(
        params_cpu, x.astype(jnp.float32) / 127.5 - 1.0, train=False))
    with jax.default_device(cpu):
        want = np.stack([np.asarray(ref_fn(jax.device_put(f[None], cpu)))[0]
                         for f in frames[:n_ref]])
    err = max(rel_l2(got[i], want[i]) for i in range(n_ref))
    # the served model computes in bfloat16 (8 mantissa bits) through 53
    # conv layers against a float32 reference: a few 1e-2 of relative L2
    # is the expected distance; a wrong layout, a dropped preprocess or
    # stale weights land at ~1. Logits, not labels: with random weights
    # the arg-max flips on rounding.
    require(err < 0.1, f"logits vs float32 CPU reference: rel L2 {err:.4f}")
    return {"frames": n, "logits_rel_l2_vs_cpu_f32": round(err, 5),
            "device": str(device)}


# --------------------------------------------------------------------------- #
# leg: the other three BASELINE.json pipelines, device decode == host decode
# --------------------------------------------------------------------------- #

def _run_model_pipeline(spec: str, size: int, frames, decoder=None):
    """appsrc → converter → filter [→ decoder] → storing sink."""
    from nnstreamer_tpu.graph import Pipeline

    p = Pipeline()
    chain = [p.add_new("appsrc", caps=_video_caps(size), data=frames),
             p.add_new("tensor_converter"),
             p.add_new("tensor_filter", framework="xla-tpu", model=spec)]
    if decoder is not None:
        mode, opts = decoder
        chain.append(p.add_new("tensor_decoder", mode=mode, async_depth=4,
                               **{f"option{k}": v for k, v in opts.items()}))
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(*chain, sink)
    p.run(timeout=600)
    require(sink.num_buffers == len(frames),
            f"{spec}: {sink.num_buffers}/{len(frames)} frames arrived")
    return p, sink.buffers


def _decoder_paths(mode: str, opts: dict, raw_buffers, config):
    """Per frame: (device submit/complete result, host decode result) of
    one decoder over the same filter outputs."""
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.decoders.base import find_decoder

    dec = find_decoder(mode)()
    dec.init({k: str(v) for k, v in opts.items()})
    pairs = []
    for buf in raw_buffers:
        require(all(m.is_device and on_tpu_array(m.device())
                    for m in buf.memories), f"{mode}: filter outputs on host")
        token = dec.submit(buf, config)
        require(isinstance(token, tuple),
                f"{mode}: device-resident tensors took the host path")
        dev_out = dec.complete(token, config)
        host_out = dec.decode(
            Buffer.of(*[np.asarray(m.host()) for m in buf.memories]), config)
        pairs.append((dev_out, host_out))
    return dec, pairs


def _same_detections(got, want, what: str) -> int:
    require(len(got) == len(want),
            f"{what}: {len(got)} detections vs {len(want)} on host")
    for a, b in zip(got, want):
        require(a["class"] == b["class"], f"{what}: class differs")
        # exp() on the chip and in NumPy differ in the last bits
        np.testing.assert_allclose(a["box"], b["box"], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(a["score"], b["score"], rtol=1e-4)
    return len(got)


def ssd_leg(tmp: str) -> dict:
    from nnstreamer_tpu.core.types import TensorsConfig
    from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors
    from nnstreamer_tpu.models.zoo import get_model

    spec = "zoo://ssd_mobilenet_v2?size=300&num_classes=91"
    priors = os.path.join(tmp, "box_priors.txt")
    n_anchors = write_box_priors(priors, size=300)
    labels = os.path.join(tmp, "labels91.txt")
    with open(labels, "w", encoding="utf-8") as f:
        f.write("\n".join(f"obj{i}" for i in range(91)))
    opts = {1: "mobilenet-ssd", 2: labels, 3: priors,
            4: "300:300", 5: "300:300"}
    frames = _frames(CFG["aux_frames"], 300, seed=2)
    _, raw = _run_model_pipeline(spec, 300, frames)
    config = TensorsConfig(get_model(spec).out_info)
    dec, pairs = _decoder_paths("bounding_box", opts, raw, config)
    kept = [_same_detections(d.meta["detections"], h.meta["detections"],
                             "ssd submit/complete")
            for d, h in pairs]
    require(sum(kept) > 0, "ssd: no detection survived on any frame")
    # the reduce really is K=256 candidates through the Mosaic kernels
    arrays = [m.device() for m in raw[0].memories[:2]]
    rows = mosaic_program(dec.epilogue_reduce(), tuple(arrays))(tuple(arrays))
    require(rows.shape == (dec.PRE_NMS_TOPK, 6) and dec.PRE_NMS_TOPK == 256,
            f"ssd reduce rows {rows.shape}")
    # the pipeline users run: reduce fused into the filter's program
    p, fused = _run_model_pipeline(spec, 300, frames,
                                   decoder=("bounding_box", opts))
    require(p._epilogue_count == 1, "ssd: decoder reduce was not fused")
    for out, (_, host) in zip(fused, pairs):
        _same_detections(out.meta["detections"], host.meta["detections"],
                         "ssd fused pipeline")
    return {"frames": len(frames), "anchors": n_anchors,
            "detections_per_frame": kept}


def deeplab_leg() -> dict:
    from nnstreamer_tpu.core.types import TensorsConfig
    from nnstreamer_tpu.models.zoo import get_model

    spec = "zoo://deeplab_v3?size=257&num_classes=21"
    opts = {1: "tflite-deeplab"}
    frames = _frames(CFG["aux_frames"], 257, seed=3)
    _, raw = _run_model_pipeline(spec, 257, frames)
    config = TensorsConfig(get_model(spec).out_info)
    dec, pairs = _decoder_paths("image_segment", opts, raw, config)
    classes = set()
    for d, h in pairs:
        canvas = h.memories[0].host()
        require(canvas.shape == (257, 257, 4), f"canvas {canvas.shape}")
        # arg-max over the same logits: exact on any backend
        np.testing.assert_array_equal(d.memories[0].host(), canvas)
        classes |= set(np.unique(canvas.reshape(-1, 4), axis=0)[:, 0])
    require(len(classes) > 1, "deeplab: one colour only")
    x = raw[0].memories[0].device()
    mosaic_program(dec.epilogue_reduce(), (x,))
    p, fused = _run_model_pipeline(spec, 257, frames,
                                   decoder=("image_segment", opts))
    require(p._epilogue_count == 1, "deeplab: colorize was not fused")
    for out, (_, host) in zip(fused, pairs):
        np.testing.assert_array_equal(out.memories[0].host(),
                                      host.memories[0].host())
    return {"frames": len(frames)}


def posenet_leg() -> dict:
    from nnstreamer_tpu.core.types import TensorsConfig
    from nnstreamer_tpu.models.zoo import get_model

    spec = "zoo://posenet?size=257"
    opts = {1: "514:514", 2: "257:257", 4: "heatmap-offset"}
    frames = _frames(CFG["aux_frames"], 257, seed=4)
    _, raw = _run_model_pipeline(spec, 257, frames)
    config = TensorsConfig(get_model(spec).out_info)
    _, pairs = _decoder_paths("pose_estimation", opts, raw, config)
    _, piped = _run_model_pipeline(spec, 257, frames,
                                   decoder=("pose_estimation", opts))
    for (d, h), out in zip(pairs, piped):
        want = np.asarray(h.meta["keypoints"])
        require(want.shape == (17, 3), f"keypoints {want.shape}")
        # same arg-max cell; float32 vs float64 position arithmetic
        np.testing.assert_allclose(np.asarray(d.meta["keypoints"]), want,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out.meta["keypoints"]), want,
                                   rtol=1e-5, atol=1e-6)
    return {"frames": len(frames)}


# --------------------------------------------------------------------------- #
# leg: every pl.pallas_call under ops/pallas, compiled, at production shapes
# --------------------------------------------------------------------------- #

def _kernel(clock: Clock, name: str, fn, ref, args, check) -> None:
    def body():
        compiled = mosaic_program(fn, *args)
        got = jax.block_until_ready(compiled(*args))
        want = jax.block_until_ready(jax.jit(ref)(*args))
        require(all(on_tpu_array(a) for a in jax.tree_util.tree_leaves(got)),
                f"{name}: result not on the TPU")
        return check(got, want)

    clock.leg(f"kernel:{name}", body)


def _exact(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        require(a.shape == b.shape and a.dtype == b.dtype,
                f"{a.shape}{a.dtype} vs {b.shape}{b.dtype}")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _dgr_check(exact: bool):
    def check(got, want):
        (q, s), (rq, rs) = got, want
        if exact:  # float32 activations: the kernel IS the reference math
            return _exact(got, want)
        # bfloat16 activations: XLA's fused bf16 gelu and the kernel's
        # (float32 inside, rounded once) can land one bf16 ulp apart. At a
        # row's largest values one bf16 ulp (2^-8) is one int8 step
        # (1/127), and the row's absmax scale can itself sit one ulp off:
        # two codes at most, on a minority of elements.
        dq = np.abs(np.asarray(q, np.int32) - np.asarray(rq, np.int32))
        require(dq.max() <= 2, f"int8 codes differ by {dq.max()}")
        require((dq > 0).mean() < 0.15,
                f"{(dq > 0).mean():.1%} of int8 codes differ")
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=2 ** -7)
        return {"codes": int(dq.size), "off_by_1": int((dq == 1).sum()),
                "off_by_2": int((dq == 2).sum())}
    return check


def _flash_check(got, want):
    # bf16 q/k/v: softmax weights round to bf16 before the PV matmul
    # (tests/test_pallas.py::test_flash_bf16_inputs_tolerance)
    np.testing.assert_allclose(
        np.asarray(got[:1, :2], np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=3e-2)


#: widest gap allowed between the decode-attention kernel's output and the
#: dense form, in units of the output's largest value. The kernel's float32
#: VPU arithmetic reads some 1e-7; the same attention with each product
#: made of three bf16 products (what precision "high" is) has to read over
#: the limit, and does
_DECODE_ATTN_LIMIT = 2e-6


def _three_pass_attention(q, k, v, pos):
    """Dense decode attention over (S, H, max_len, hd) rows ``<= pos``
    with every product as ``hi*hi + hi*lo + lo*hi`` of bf16 halves."""
    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def dot3(eq, x, y):
        (xh, xl), (yh, yl) = split(x), split(y)
        with jax.default_matmul_precision("float32"):
            return (jnp.einsum(eq, xh, yh) + jnp.einsum(eq, xh, yl)
                    + jnp.einsum(eq, xl, yh))

    s = dot3("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    live = jnp.arange(k.shape[2]) <= pos[:, None, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    return dot3("bhqk,bhkd->bhqd", p, v)


def _decode_attention_kernel(clock: Clock, rng) -> None:
    """``pallas.decode_attention`` at the benchmark's decode shape against
    the dense masked form: lengths at 1, a block's edge and either side of
    it, the last row, and a slot that holds no request."""
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    s, h, m, hd = CFG["decode_attn"]
    layers = CFG["decode_attn_layers"]
    li = layers - 1
    bk = da.kv_block(m)
    pos = jnp.asarray([1, bk - 1, bk, bk + 1, 1000, m - 1, 77, 2 * bk],
                      jnp.int32)
    active = np.asarray([True] * 6 + [False, True])
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32))
    args = [normal(s, h, 1, hd), normal(s, h, 1, hd), normal(s, h, 1, hd),
            normal(s, layers, h, m, hd), normal(s, layers, h, m, hd),
            jnp.int32(li), pos, jnp.asarray(active)]

    def dense(*a):
        with jax.default_matmul_precision("float32"):
            return da.window_attention_reference(*a, layer_axis=1)

    def check(got, want):
        _exact(got[1:], want[1:])       # the stores: new rows, bit for bit
        o = np.asarray(want[0])
        three = np.asarray(jax.jit(_three_pass_attention)(
            args[0], want[1][:, li], want[2][:, li], pos))
        gap = float(np.abs(np.asarray(got[0]) - o).max() / np.abs(o).max())
        control = float(np.abs(three - o)[active].max() / np.abs(o).max())
        require(gap <= _DECODE_ATTN_LIMIT,
                f"decode_attention vs dense: gap {gap:.2e}")
        require(control > _DECODE_ATTN_LIMIT,
                f"the limit lets three bf16 passes through: {control:.2e}")
        return {"gap": float(f"{gap:.2e}"),
                "gap_of_three_bf16_passes": float(f"{control:.2e}")}

    _kernel(clock, "decode_attention_s{}h{}L{}d{}".format(s, h, m, hd),
            functools.partial(da.decode_attention, layer_axis=1), dense,
            args, check)


def kernels_leg(clock: Clock) -> dict:
    from nnstreamer_tpu.ops.pallas import epilogue as ep
    from nnstreamer_tpu.ops.pallas import preprocess as pp
    from nnstreamer_tpu.ops.pallas.flash_attention import flash_attention
    from nnstreamer_tpu.parallel.ring import reference_attention

    rng = np.random.default_rng(5)
    f32 = np.float32

    k = 256  # decoders.bounding_box.PRE_NMS_TOPK
    x0, y0 = rng.uniform(0, 0.8, (2, k)).astype(f32)
    x1 = x0 + rng.uniform(0.05, 0.3, k).astype(f32)
    y1 = y0 + rng.uniform(0.05, 0.3, k).astype(f32)
    scores = np.sort(rng.uniform(0, 1, k).astype(f32))[::-1].copy()
    boxes = [jnp.asarray(v) for v in (x0, y0, x1, y1, scores)]
    _kernel(clock, "nms_sweep_k256",
            lambda *a: ep.nms_sweep(*a, iou_threshold=0.5, threshold=0.25),
            lambda *a: ep.nms_sweep_reference(*a, 0.5, 0.25), boxes, _exact)

    # the reference's ssd_mobilenet .tflite: 1917 anchors x 91 classes
    # less background (the zoo model's 2916 anchors run in the SSD leg)
    cls = jnp.asarray(rng.normal(size=(1917, 90)).astype(f32))
    _kernel(clock, "class_reduce_1917x90", ep.class_reduce,
            ep.class_reduce_reference, [cls], _exact)

    from nnstreamer_tpu.decoders.image_segment import _PALETTE

    logits = jnp.asarray(rng.normal(size=(257, 257, 21)).astype(f32))
    _kernel(clock, "segment_colorize_257x257x21",
            lambda x: ep.segment_colorize(x, _PALETTE),
            lambda x: ep.segment_colorize_reference(x, _PALETTE),
            [logits], _exact)
    ids = jnp.asarray(rng.integers(0, 21, (257, 257)).astype(f32))
    _kernel(clock, "segment_colorize_pre_argmaxed",
            lambda x: ep.segment_colorize(x, _PALETTE, pre_argmaxed=True),
            lambda x: ep.segment_colorize_reference(x, _PALETTE,
                                                    pre_argmaxed=True),
            [ids], _exact)

    def dgr_inputs(shape_y, f):
        y = jnp.asarray(rng.integers(-20000, 20000, shape_y)
                        .astype(np.int32))
        xs = jnp.asarray(rng.uniform(1e-3, 1e-2, shape_y[:-1] + (1,))
                         .astype(f32))
        ws = jnp.asarray(rng.uniform(1e-3, 1e-2, (f,)).astype(f32))
        return [y, xs, ws]

    for f in CFG["dgr_f"]:
        for r in CFG["dgr_rows"]:
            for dt in (jnp.bfloat16, jnp.float32):
                _kernel(clock,
                        f"dequant_gelu_requant_f{f}_r{r}_{jnp.dtype(dt).name}",
                        functools.partial(ep.dequant_gelu_requant,
                                          out_dtype=dt),
                        functools.partial(ep.dequant_gelu_requant_reference,
                                          out_dtype=dt),
                        dgr_inputs((r, f), f), _dgr_check(dt == jnp.float32))
    # the slot engine's form: one row per slot under vmap (ops/int8.py)
    f = CFG["dgr_f"][0]
    _kernel(clock, "dequant_gelu_requant_slot_vmap",
            jax.vmap(ep.dequant_gelu_requant, in_axes=(0, 0, None)),
            jax.vmap(ep.dequant_gelu_requant_reference,
                     in_axes=(0, 0, None)),
            dgr_inputs((CFG["lm_slots"], 1, f), f), _dgr_check(False))

    def dense(q, kk, v):  # two heads of one batch row bound the memory
        q, kk, v = (t[:1, :2].astype(jnp.float32) for t in (q, kk, v))
        return reference_attention(q, kk, v, causal=True)

    def flash_residuals(q, kk, v):
        acc, _, l = flash_attention(q, kk, v, return_residuals=True)
        return acc / jnp.maximum(l, 1e-30)[..., None]

    for shape in CFG["flash"]:
        qkv = [jnp.asarray(rng.standard_normal(shape).astype(f32),
                           jnp.bfloat16) for _ in range(3)]
        tag = "b{}h{}L{}d{}".format(*shape)
        _kernel(clock, f"flash_attention_{tag}", flash_attention, dense,
                qkv, _flash_check)
        _kernel(clock, f"flash_attention_residuals_{tag}", flash_residuals,
                dense, qkv, _flash_check)

    _decode_attention_kernel(clock, rng)

    frame = jnp.asarray(rng.integers(0, 256, (1, 224, 224, 3))
                        .astype(np.uint8))
    _kernel(clock, "normalize_u8_224", pp.normalize_u8,
            functools.partial(pp.normalize_u8_reference, scale=1 / 127.5,
                              bias=-1.0), [frame], _exact)
    fframe = jnp.asarray(rng.uniform(-1, 1, (1, 224, 224, 3)).astype(f32))
    _kernel(clock, "quantize_affine_224",
            lambda x: pp.quantize_affine(x, 1 / 127.5, 128),
            lambda x: pp.quantize_affine_reference(x, 1 / 127.5, 128),
            [fframe], _exact)
    return {"kernels": sum(r["leg"].startswith("kernel:")
                           for r in clock.legs)}


# --------------------------------------------------------------------------- #
# leg: serving — LMEngine at V8192·d1024·H8·L8, 8 slots
# --------------------------------------------------------------------------- #
# Float32 weights: the causal_lm family pins float32 matmul precision because
# its contract is exactness between execution forms (prefill / decode / paged /
# sharded), and that contract is what this leg checks. LMEngine cannot serve a
# bfloat16 tree today — its float32 KV cache promotes the scan carry
# (PERF.md, open questions); bf16 runs in the kernels leg.

def lm_requests():
    """(prompt, submit kwargs): a shared prefix, lengths in two prefill
    buckets, greedy and sampled mixed."""
    V = CFG["lm_dims"][0]
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, V, CFG["lm_prefix"]).astype(np.int32)
    reqs = []
    for i, n in enumerate(CFG["lm_prompts"]):
        tail = rng.integers(0, V, n - prefix.size).astype(np.int32)
        kw = dict(temperature=0.8, top_k=40, seed=i) if i % 3 == 2 else {}
        reqs.append((np.concatenate([prefix, tail]), kw))
    return reqs


def _two_buckets(n: int) -> int:
    lens = CFG["lm_prompts"]
    small = lens[len(lens) // 2 - 1]
    return small if n <= small else max(lens)


def run_engine(engine_cls, params, reqs, *engine_args, **engine_kw):
    H = CFG["lm_dims"][2]
    eng = engine_cls(params, H, CFG["lm_max_len"], *engine_args,
                     n_slots=CFG["lm_slots"], bucket=_two_buckets,
                     **engine_kw)
    rids = [eng.submit(p, max_new=CFG["lm_new"], **kw) for p, kw in reqs]
    done = eng.run()
    outs = [done[r] for r in rids]
    V = CFG["lm_dims"][0]
    for o in outs:
        require(len(o) == CFG["lm_new"] and all(0 <= t < V for t in o),
                f"request returned {len(o)} tokens")
    return eng, outs


def _greedy_equal(a, b, reqs, what: str) -> None:
    for i, (_, kw) in enumerate(reqs):
        if not kw:
            require(a[i] == b[i], f"{what}: greedy request {i} differs")


def serving_leg(state: dict) -> dict:
    from nnstreamer_tpu.models import causal_lm
    from nnstreamer_tpu.serving import LMEngine

    V, D, H, L = CFG["lm_dims"]
    params = state["params"] = causal_lm.init_causal_lm(
        jax.random.PRNGKey(0), V, D, H, L, CFG["lm_max_len"])
    reqs = state["reqs"] = lm_requests()

    eng, cont = run_engine(LMEngine, params, reqs, kv_page_size=0)
    require(on_tpu_array(eng._kc), "contiguous KV store not on the TPU")
    state["tokens"] = cont
    # paged KV, same requests: the shared prefix must hit the radix tree
    peng, paged = run_engine(LMEngine, params, reqs, kv_page_size=16)
    _greedy_equal(cont, paged, reqs, "paged vs contiguous")
    require(peng.prefix_hit_rate > 0, f"no prefix hit: {peng.kv_stats}")
    sampled_equal = all(cont[i] == paged[i] for i in range(len(reqs)))

    # one prompt's prefill logits (the engine's own prefill function: a
    # padded bucket with a length mask) against the plain full forward
    prompt = reqs[3][0]
    tb = _two_buckets(prompt.size)
    padded = np.zeros((1, tb), np.int32)
    padded[0, :prompt.size] = prompt
    logits = causal_lm.lm_prefill_masked(
        params, jnp.asarray(padded), jnp.int32(prompt.size), H,
        CFG["lm_max_len"])[0][0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(causal_lm.lm_forward, n_heads=H))(
            params, jnp.asarray(prompt[None]))[0, -1]
    err = rel_l2(logits, want)
    # the same float32 math over a different shape: only the order of the
    # sums differs. A broken mask or cache layout gives ~1.
    require(logits.shape == (V,) and err < 1e-4,
            f"prefill logits vs lm_forward: rel L2 {err:.2e}")
    return {"requests": len(reqs), "new_tokens": CFG["lm_new"],
            "prefix_hit_rate": round(peng.prefix_hit_rate, 3),
            "sampled_equal_paged": sampled_equal,
            "prefill_rel_l2_vs_forward": float(f"{err:.2e}")}


def serving_w8a8_leg(state: dict) -> dict:
    """The same requests through a quantize_lm_params tree: the int8 MLP
    epilogue kernel inside the engine's prefill and vmapped decode."""
    from nnstreamer_tpu.models import causal_lm
    from nnstreamer_tpu.serving import LMEngine

    H = CFG["lm_dims"][2]
    params, reqs = state["params"], state["reqs"]
    qparams = causal_lm.quantize_lm_params(params)
    run_engine(LMEngine, qparams, reqs, kv_page_size=0)
    prompt = reqs[3][0]
    toks = jnp.asarray(prompt[None])
    prefill = functools.partial(causal_lm.lm_prefill, n_heads=H,
                                max_len=CFG["lm_max_len"])
    qlogits = mosaic_program(prefill, qparams, toks)(qparams, toks)[0]
    logits = jax.jit(prefill)(params, toks)[0]
    err = rel_l2(qlogits, logits)
    # per-channel int8 weights and per-row int8 activations: ~1% per
    # GEMM, 32 GEMMs deep. Measures the quantization, bounds a bug.
    require(err < 0.15, f"w8a8 prefill logits vs float: rel L2 {err:.4f}")
    return {"requests": len(reqs), "w8a8_rel_l2_vs_float": round(err, 4)}


def flash_prefill_leg(state: dict) -> dict:
    """causal_lm.lm_prefill(flash=True) against the dense prefill: the
    engine's masked prefill never takes the flash branch."""
    from nnstreamer_tpu.models import causal_lm

    H = CFG["lm_dims"][2]
    params = state["params"]
    prompt = state["reqs"][-1][0]
    toks = jnp.asarray(prompt[None])
    kw = dict(n_heads=H, max_len=CFG["lm_max_len"])
    dense = jax.jit(functools.partial(causal_lm.lm_prefill, flash=False,
                                      **kw))(params, toks)
    fn = functools.partial(causal_lm.lm_prefill, flash=True, **kw)
    flash = mosaic_program(fn, params, toks)(params, toks)
    err = rel_l2(flash[0], dense[0])
    # same float32 weights; inside the kernel the MXU may take float32
    # operands in bf16 passes, the dense path runs at float32 precision
    require(err < 2e-2, f"flash prefill logits vs dense: rel L2 {err:.2e}")
    kv_err = max(rel_l2(flash[1], dense[1]), rel_l2(flash[2], dense[2]))
    require(kv_err < 2e-2, f"flash prefill KV vs dense: rel L2 {kv_err:.2e}")
    return {"prompt": int(prompt.size),
            "flash_rel_l2_vs_dense": float(f"{err:.2e}")}


# --------------------------------------------------------------------------- #
# leg: four chips
# --------------------------------------------------------------------------- #

def _distinct_devices(tree) -> int:
    return min(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(tree))


def multichip_leg(state: dict) -> dict:
    from nnstreamer_tpu.core import Caps, TensorsConfig
    from nnstreamer_tpu.graph import Pipeline
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import (auto_mesh_2d, make_mesh,
                                         shard_params, sharded_bundle)
    from nnstreamer_tpu.serving import TPLMEngine

    n = 4
    mesh = auto_mesh_2d(n)
    batch = 2 * mesh.shape["data"]
    bundle = get_model(_MNV2 + f"&batch={batch}")
    frames = np.stack(_frames(batch, 224, seed=7))
    p = Pipeline()
    src = p.add_new("appsrc", data=[frames],
                    caps=Caps.tensors(TensorsConfig(bundle.in_info)))
    filt = p.add_new("tensor_filter", framework="xla-tpu",
                     model=sharded_bundle(bundle, mesh))
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, filt, sink)
    p.run(timeout=600)
    require(sink.num_buffers == 1, "sharded filter emitted nothing")
    out = sink.buffers[0].memories[0].device()
    want = jax.jit(bundle.fn())(frames)
    require(_distinct_devices(out) == n,
            f"sharded filter output on {_distinct_devices(out)} devices")
    require(_distinct_devices(shard_params(bundle.params, mesh)) == n,
            "sharded params not on four devices")
    err = rel_l2(out, want)
    # same bf16 program; the model-axis collectives reorder float32 sums
    require(err < 2e-2, f"sharded vs single-device logits: rel L2 {err:.4f}")

    tp, sharded = run_engine(
        TPLMEngine, state["params"], state["reqs"],
        make_mesh({"model": n}, devices=jax.devices()[:n]))
    require(sharded == state["tokens"],
            "TP engine tokens differ from the single-device engine's")
    require(_distinct_devices(tp._tp) == n and _distinct_devices(tp._kc) == n,
            "TP engine params / KV cache not on four devices")
    return {"devices": n, "mesh": dict(mesh.shape),
            "sharded_rel_l2": round(err, 5), "tp_tokens_equal": True}


# --------------------------------------------------------------------------- #

def main() -> int:
    devices = jax.devices()
    if not hw.on_tpu(devices[0]):
        print(f"chip_smoke: no TPU — JAX found {devices[0].platform} "
              f"({devices[0].device_kind}); this script measures nothing "
              "on a CPU", file=sys.stderr)
        return 2
    cache_dir = hw.enable_compile_cache()
    clock = Clock()
    t0 = time.monotonic()
    print(f"[smoke] compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          " entries at start)", flush=True)
    gate = clock.leg("gate", gate_leg)
    state: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        clock.leg("stream:cli", cli_leg, tmp)
        clock.leg("stream:pipeline", pipeline_leg)
        clock.leg("stream:ssd_mobilenet+bounding_box", ssd_leg, tmp)
    clock.leg("stream:deeplab_v3+image_segment", deeplab_leg)
    clock.leg("stream:posenet+pose_estimation", posenet_leg)
    clock.leg("kernels", kernels_leg, clock)
    clock.leg("serving:contiguous+paged", serving_leg, state)
    clock.leg("serving:w8a8", serving_w8a8_leg, state)
    clock.leg("serving:flash_prefill", flash_prefill_leg, state)
    if len(devices) >= 4:
        multichip = clock.leg("multichip", multichip_leg, state)
    else:
        multichip = f"skipped: {len(devices)} device"
    print("[smoke] " + json.dumps({
        "legs": len(clock.legs), "compile_s": round(clock.compile_s, 1),
        "wall_s": round(time.monotonic() - t0, 1),
        "cache": {"dir": cache_dir, "hits": clock.cache_hits,
                  "misses": clock.cache_misses},
        "native_runtime": gate["native_runtime"],
        "multichip": multichip}), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": gate["platform"],
                   "kind": gate["device_kind"], "count": gate["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
