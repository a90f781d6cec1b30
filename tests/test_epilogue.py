"""Epilogue fusion tests: ops/epilogue.py + ops/pallas/epilogue.py.

The contract under test is bit-identity: a fused pipeline (post-filter
chain compiled into the filter's jit) must produce exactly what the
unfused element-by-element pipeline produces, for every fused stage
kind — transforms, passthrough converters, and reduce-capable decoders.
Kernel tests run the Pallas programs in interpret mode against their
jnp references; pipeline tests diff fused vs unfused end-to-end.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
from nnstreamer_tpu.core.buffer import Buffer, TensorMemory
from nnstreamer_tpu.graph import Pipeline
from nnstreamer_tpu.ops.pallas import epilogue as ep


def caps_of(dims, types, rate=30):
    return Caps.tensors(TensorsConfig(TensorsInfo.from_strings(dims, types),
                                      rate))


# --------------------------------------------------------------------------- #
# Pallas kernels vs references (interpret mode)
# --------------------------------------------------------------------------- #

class TestKernels:
    def _boxes(self, k, seed=0):
        rng = np.random.default_rng(seed)
        x0 = rng.uniform(0, 0.8, k).astype(np.float32)
        y0 = rng.uniform(0, 0.8, k).astype(np.float32)
        x1 = x0 + rng.uniform(0.05, 0.3, k).astype(np.float32)
        y1 = y0 + rng.uniform(0.05, 0.3, k).astype(np.float32)
        scores = np.sort(rng.uniform(0, 1, k).astype(np.float32))[::-1].copy()
        return tuple(jnp.asarray(v) for v in (x0, y0, x1, y1, scores))

    @pytest.mark.parametrize("k", [32, 37])  # aligned + non-lane-aligned
    def test_nms_sweep_bit_exact(self, k):
        x0, y0, x1, y1, s = self._boxes(k, seed=k)
        ref = ep.nms_sweep_reference(x0, y0, x1, y1, s, 0.5, 0.25)
        got = ep.nms_sweep(x0, y0, x1, y1, s, iou_threshold=0.5,
                           threshold=0.25, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_class_reduce_bit_exact(self):
        rng = np.random.default_rng(1)
        cls = jnp.asarray(rng.normal(size=(123, 20)).astype(np.float32))
        rs, ri = ep.class_reduce_reference(cls)
        ks, ki = ep.class_reduce(cls, interpret=True)
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(ks))
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))

    def test_class_reduce_tie_break_first_max(self):
        cls = jnp.asarray(np.array([[1.0, 3.0, 3.0, 0.0],
                                    [2.0, 2.0, 2.0, 2.0]], np.float32))
        _, ri = ep.class_reduce_reference(cls)
        _, ki = ep.class_reduce(cls, interpret=True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))

    def _palette(self):
        pal = np.zeros((256, 4), np.uint8)
        pal[1:, :3] = np.arange(1, 256)[:, None] * np.array([3, 5, 7]) % 256
        pal[1:, 3] = 160
        return pal

    def test_segment_colorize_logits_bit_exact(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(33, 41, 21)).astype(np.float32))
        pal = self._palette()
        ref = ep.segment_colorize_reference(logits, pal)
        got = ep.segment_colorize(logits, pal, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_segment_colorize_pre_argmaxed_bit_exact(self):
        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(0, 21, (33, 41)).astype(np.float32))
        pal = self._palette()
        ref = ep.segment_colorize_reference(ids, pal, pre_argmaxed=True)
        got = ep.segment_colorize(ids, pal, pre_argmaxed=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def _dgr_inputs(self, r=17, f=130, seed=4):
        rng = np.random.default_rng(seed)
        y = jnp.asarray(rng.integers(-2000, 2000, (r, f)).astype(np.int32))
        xs = jnp.asarray(rng.uniform(1e-3, 1e-2, (r, 1)).astype(np.float32))
        ws = jnp.asarray(rng.uniform(1e-3, 1e-2, (f,)).astype(np.float32))
        return y, xs, ws

    def test_dequant_gelu_requant_f32_bit_exact(self):
        # the reference must itself be jitted: eager XLA contracts the
        # dequant multiply chain differently (1-ulp scale drift), and the
        # production comparison is always jit-vs-jit
        y, xs, ws = self._dgr_inputs()
        ref = jax.jit(functools.partial(ep.dequant_gelu_requant_reference,
                                        out_dtype=jnp.float32))
        rq, rs = ref(y, xs, ws)
        kq, ks = ep.dequant_gelu_requant(y, xs, ws, out_dtype=jnp.float32,
                                         interpret=True)
        np.testing.assert_array_equal(np.asarray(rq), np.asarray(kq))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(ks))

    def test_dequant_gelu_requant_bf16_close(self):
        # pallas interpret mode evaluates bf16 intermediates in f32, so
        # bf16 can't be asserted bit-exact off-TPU: quantized codes must
        # land within 1 and scales within bf16 epsilon of the reference
        y, xs, ws = self._dgr_inputs(seed=5)
        ref = jax.jit(functools.partial(ep.dequant_gelu_requant_reference,
                                        out_dtype=jnp.bfloat16))
        rq, rs = ref(y, xs, ws)
        kq, ks = ep.dequant_gelu_requant(y, xs, ws, out_dtype=jnp.bfloat16,
                                         interpret=True)
        dq = np.abs(np.asarray(rq, np.int32) - np.asarray(kq, np.int32))
        assert dq.max() <= 1
        np.testing.assert_allclose(np.asarray(rs, np.float32),
                                   np.asarray(ks, np.float32), rtol=1e-2)

    def test_dequant_gelu_requant_zero_row_scale(self):
        # an all-zero row must emit scale 1.0, not 0/127 (div-by-zero in
        # the consumer's dequant otherwise)
        y = jnp.zeros((4, 130), jnp.int32)
        xs = jnp.full((4, 1), 1e-3, jnp.float32)
        ws = jnp.full((130,), 1e-3, jnp.float32)
        q, s = ep.dequant_gelu_requant(y, xs, ws, out_dtype=jnp.float32,
                                       interpret=True)
        assert np.all(np.asarray(q) == 0)
        np.testing.assert_array_equal(np.asarray(s), np.ones((4, 1), np.float32))


def _tpu_module_text(fn, *specs):
    """StableHLO of ``fn`` lowered for the TPU platform from this CPU
    host — runs Pallas's TPU lowering (not Mosaic's own passes, which
    only the chip runs: chip_smoke.py)."""
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *specs).mlir_module()


_S = jax.ShapeDtypeStruct
_PAL = np.zeros((256, 4), np.uint8)
#: every epilogue kernel at the shape its production caller uses
_TPU_LOWERING_CASES = {
    "nms_sweep_k256": (
        lambda *a: ep.nms_sweep(*a, iou_threshold=0.5, threshold=0.5),
        [_S((256,), jnp.float32)] * 5),
    "class_reduce_1917x90": (ep.class_reduce, [_S((1917, 90), jnp.float32)]),
    "segment_colorize_257x257x21": (
        lambda x: ep.segment_colorize(x, _PAL),
        [_S((257, 257, 21), jnp.float32)]),
    "segment_colorize_pre_argmaxed": (
        lambda x: ep.segment_colorize(x, _PAL, pre_argmaxed=True),
        [_S((257, 257), jnp.float32)]),
    **{f"dequant_gelu_requant_f{f}_r{r}": (
        ep.dequant_gelu_requant,
        [_S((r, f), jnp.int32), _S((r, 1), jnp.float32),
         _S((f,), jnp.float32)])
       for f in (4 * 1024, 4 * 4096) for r in (8, 2048)},
    "dequant_gelu_requant_slot_vmap": (
        jax.vmap(ep.dequant_gelu_requant, in_axes=(0, 0, None)),
        [_S((8, 1, 4096), jnp.int32), _S((8, 1, 1), jnp.float32),
         _S((4096,), jnp.float32)]),
}


@pytest.mark.parametrize("case", sorted(_TPU_LOWERING_CASES))
def test_kernel_lowers_for_tpu(case):
    """A Pallas-TPU lowering refusal (e.g. a value-level dynamic_slice)
    is caught here without a chip, and the TPU program really carries
    the Mosaic call rather than the jnp reference."""
    fn, specs = _TPU_LOWERING_CASES[case]
    assert "tpu_custom_call" in _tpu_module_text(fn, *specs)


def test_kernel_lowers_reference_off_tpu():
    """The same entry point placed on a CPU lowers the jnp reference:
    selection follows the lowering platform, not the process default."""
    fn, specs = _TPU_LOWERING_CASES["nms_sweep_k256"]
    assert "tpu_custom_call" not in jax.jit(fn).lower(*specs).as_text()


class TestMlpMatmul:
    def test_quantized_fused_matches_unfused(self):
        from nnstreamer_tpu.ops import int8

        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
        w1 = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32) * 0.1)
        w2 = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32) * 0.1)
        q1, q2 = int8.quantize_weight(w1), int8.quantize_weight(w2)
        fused = jax.jit(int8.mlp_matmul)(x, q1, q2)
        unfused = jax.jit(lambda x: int8.matmul_any(
            jax.nn.gelu(int8.matmul_any(x, q1)), q2))(x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   rtol=2e-2, atol=2e-2)

    def test_unquantized_passthrough_exact(self):
        from nnstreamer_tpu.ops import int8

        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32))
        w1 = jnp.asarray(rng.normal(size=(8, 12)).astype(np.float32))
        w2 = jnp.asarray(rng.normal(size=(12, 4)).astype(np.float32))
        got = jax.jit(int8.mlp_matmul)(x, w1, w2)
        want = jax.jit(lambda x: jax.nn.gelu(x @ w1) @ w2)(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------- #
# pipeline-level fused vs unfused bit-identity
# --------------------------------------------------------------------------- #

def _run_pair(build):
    """build(auto_fuse) -> (pipeline, sink); returns both runs."""
    pf, sf = build(True)
    pu, su = build(False)
    return pf, sf, pu, su


class TestPipelineFusion:
    def test_transform_chain_fused_bit_identical(self):
        data = [np.linspace(-2, 2, 8, dtype=np.float32).reshape(1, 8)]

        def build(auto_fuse):
            p = Pipeline()
            p.auto_fuse = auto_fuse
            src = p.add_new("appsrc", caps=caps_of("8:1", "float32"),
                            data=data)
            f = p.add_new("tensor_filter", model=lambda x: jnp.tanh(x))
            t1 = p.add_new("tensor_transform", mode="arithmetic",
                           option="mul:3.0,add:0.25")
            t2 = p.add_new("tensor_transform", mode="clamp", option="-0.5:2.5")
            sink = p.add_new("tensor_sink", store=True)
            Pipeline.link(src, f, t1, t2, sink)
            p.run(timeout=60)
            return p, sink

        pf, sf, pu, su = _run_pair(build)
        assert pf._epilogue_count == 2
        assert pu._epilogue_count == 0
        np.testing.assert_array_equal(sf.buffers[0].memories[0].host(),
                                      su.buffers[0].memories[0].host())

    def test_converter_passthrough_fused(self):
        data = [np.ones((1, 4), np.float32) * 7]

        def build(auto_fuse):
            p = Pipeline()
            p.auto_fuse = auto_fuse
            src = p.add_new("appsrc", caps=caps_of("4:1", "float32"),
                            data=data)
            f = p.add_new("tensor_filter", model=lambda x: x * 2 + 1)
            conv = p.add_new("tensor_converter")
            sink = p.add_new("tensor_sink", store=True)
            Pipeline.link(src, f, conv, sink)
            p.run(timeout=60)
            return p, sink

        pf, sf, pu, su = _run_pair(build)
        assert pf._epilogue_count == 1
        assert pu._epilogue_count == 0
        np.testing.assert_array_equal(sf.buffers[0].memories[0].host(),
                                      su.buffers[0].memories[0].host())

    def _ssd_build(self, tmp_path, auto_fuse, async_depth=0):
        from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

        priors = tmp_path / "priors.txt"
        n = write_box_priors(str(priors), size=96)
        rng = np.random.default_rng(8)
        flat = np.concatenate(
            [rng.normal(size=(1, n * 4)).astype(np.float32),
             rng.normal(size=(1, n * 6)).astype(np.float32) * 4], axis=1)

        def model(x, n=n):
            return (x[:, :n * 4].reshape(1, n, 4),
                    x[:, n * 4:].reshape(1, n, 6))

        p = Pipeline()
        p.auto_fuse = auto_fuse
        src = p.add_new("appsrc", caps=caps_of(f"{n * 10}:1", "float32"),
                        data=[flat])
        f = p.add_new("tensor_filter", model=model)
        kw = {"async_depth": async_depth} if async_depth else {}
        dec = p.add_new("tensor_decoder", mode="bounding_box",
                        option1="mobilenet-ssd", option3=str(priors),
                        option4="96:96", option5="96:96", **kw)
        sink = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, f, dec, sink)
        p.run(timeout=120)
        return p, sink

    def test_ssd_decoder_fused_matches_unfused(self, tmp_path):
        pf, sf = self._ssd_build(tmp_path, True)
        pu, su = self._ssd_build(tmp_path, False)
        assert pf._epilogue_count == 1
        assert pu._epilogue_count == 0
        h = su.buffers[0].meta["detections"]
        d = sf.buffers[0].meta["detections"]
        assert len(d) > 0 and len(h) == len(d)
        for a, b in zip(h, d):
            assert a["class"] == b["class"]
            np.testing.assert_allclose(a["box"], b["box"], rtol=1e-4,
                                       atol=1e-5)
            np.testing.assert_allclose(a["score"], b["score"], rtol=1e-4)
        assert sf.buffers[0].memories[0].host().shape == \
            su.buffers[0].memories[0].host().shape

    def test_ssd_decoder_fused_async_depth(self, tmp_path):
        # async submit/complete path with the fused reduce: the tuple
        # token carries the pre-reduced rows through the depth queue
        pf, sf = self._ssd_build(tmp_path, True, async_depth=2)
        pu, su = self._ssd_build(tmp_path, True)
        assert pf._epilogue_count == 1
        np.testing.assert_array_equal(sf.buffers[0].memories[0].host(),
                                      su.buffers[0].memories[0].host())
        assert sf.buffers[0].meta["detections"] == \
            su.buffers[0].meta["detections"]

    def test_image_segment_fused_bit_identical(self):
        h, w, classes = 13, 11, 5
        rng = np.random.default_rng(9)
        logits = rng.normal(size=(1, h, w, classes)).astype(np.float32)

        def build(auto_fuse):
            p = Pipeline()
            p.auto_fuse = auto_fuse
            src = p.add_new("appsrc",
                            caps=caps_of(f"{classes}:{w}:{h}:1", "float32"),
                            data=[logits])
            f = p.add_new("tensor_filter", model=lambda x: x * 1.5)
            dec = p.add_new("tensor_decoder", mode="image_segment",
                            option1="tflite-deeplab")
            sink = p.add_new("tensor_sink", store=True)
            Pipeline.link(src, f, dec, sink)
            p.run(timeout=60)
            return p, sink

        pf, sf, pu, su = _run_pair(build)
        assert pf._epilogue_count == 1
        assert pu._epilogue_count == 0
        fused = sf.buffers[0].memories[0].host()
        plain = su.buffers[0].memories[0].host()
        assert fused.shape == (h, w, 4)
        np.testing.assert_array_equal(fused, plain)

    def test_auto_fuse_off_is_opt_out(self):
        data = [np.ones((1, 4), np.float32)]
        p = Pipeline()
        p.auto_fuse = False
        src = p.add_new("appsrc", caps=caps_of("4:1", "float32"), data=data)
        f = p.add_new("tensor_filter", model=lambda x: x + 1)
        t = p.add_new("tensor_transform", mode="typecast", option="float32")
        sink = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, f, t, sink)
        p.run(timeout=60)
        assert p._epilogue_count == 0
        assert not t._fused_post

    def test_select_hook_can_veto(self, monkeypatch):
        from nnstreamer_tpu.ops import epilogue as epi

        calls = []

        def veto(filter_label, chain_labels):
            calls.append((filter_label, list(chain_labels)))
            return False

        monkeypatch.setattr(epi, "EPILOGUE_SELECT_HOOK", veto)
        data = [np.ones((1, 4), np.float32)]
        p = Pipeline()
        src = p.add_new("appsrc", caps=caps_of("4:1", "float32"), data=data)
        f = p.add_new("tensor_filter", model=lambda x: x + 1)
        t = p.add_new("tensor_transform", mode="typecast", option="float32")
        sink = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, f, t, sink)
        p.run(timeout=60)
        assert p._epilogue_count == 0
        assert len(calls) == 1
        assert calls[0][1] == [t.name]

    def test_fusion_stops_at_branching(self):
        data = [np.ones((1, 4), np.float32)]
        p = Pipeline()
        src = p.add_new("appsrc", caps=caps_of("4:1", "float32"), data=data)
        f = p.add_new("tensor_filter", model=lambda x: x + 1)
        t = p.add_new("tensor_transform", mode="typecast", option="float32")
        tee = p.add_new("tee")
        q1 = p.add_new("queue")
        s1 = p.add_new("tensor_sink", store=True)
        q2 = p.add_new("queue")
        s2 = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, f, t, tee)
        Pipeline.link(tee, q1, s1)
        Pipeline.link(tee, q2, s2)
        p.run(timeout=60)
        # the chain ends at the tee: the transform still fuses (it is
        # upstream of the branch point with single pads)
        assert p._epilogue_count == 1
        np.testing.assert_array_equal(s1.buffers[0].memories[0].host(),
                                      s2.buffers[0].memories[0].host())


# --------------------------------------------------------------------------- #
# decoder-level fused contract for the modes without a pipeline harness
# --------------------------------------------------------------------------- #

class TestDecoderReduceModes:
    def _fused_roundtrip(self, make, arrays, cfg):
        """host decode vs epilogue_reduce applied out-of-band (what the
        fused filter jit does) + decode on the pre-reduced buffer."""
        host_out = make().decode(Buffer.of(*arrays), cfg)
        d = make()
        red = d.epilogue_reduce()
        assert red is not None
        rows = jax.jit(red)(tuple(jnp.asarray(a) for a in arrays))
        d._fused_epilogue = True
        fused_out = d.decode(Buffer.of(np.asarray(rows)), cfg)
        return host_out, fused_out

    @staticmethod
    def _same_detections(host_out, fused_out):
        h = host_out.meta["detections"]
        d = fused_out.meta["detections"]
        assert len(h) == len(d) > 0
        for a, b in zip(h, d):
            assert a["class"] == b["class"]
            np.testing.assert_allclose(a["box"], b["box"], rtol=1e-5,
                                       atol=1e-6)
            np.testing.assert_allclose(a["score"], b["score"], rtol=1e-5)

    def test_postprocess_mode(self, tmp_path):
        from nnstreamer_tpu.decoders.base import find_decoder

        labels = tmp_path / "l.txt"
        labels.write_text("person\ncar\n")
        boxes = np.array([[[0.1, 0.1, 0.5, 0.5],
                           [0.6, 0.6, 0.9, 0.9]]], np.float32)
        classes = np.array([[0, 1]], np.float32)
        scores = np.array([[0.9, 0.8]], np.float32)
        count = np.array([2], np.float32)
        cfg = TensorsConfig(TensorsInfo.from_strings(
            "4:2:1,2:1,2:1,1", "float32"))

        def make():
            d = find_decoder("bounding_box")()
            d.init({1: "mobilenet-ssd-postprocess", 2: str(labels),
                    4: "160:120", 5: "300:300"})
            return d

        host_out, fused_out = self._fused_roundtrip(
            make, (boxes, classes, scores, count), cfg)
        self._same_detections(host_out, fused_out)
        np.testing.assert_array_equal(host_out.memories[0].host(),
                                      fused_out.memories[0].host())

    def test_postprocess_count_caps_rows(self, tmp_path):
        from nnstreamer_tpu.decoders.base import find_decoder

        boxes = np.array([[[0.1, 0.1, 0.5, 0.5],
                           [0.6, 0.6, 0.9, 0.9]]], np.float32)
        classes = np.array([[0, 1]], np.float32)
        scores = np.array([[0.9, 0.8]], np.float32)
        count = np.array([1], np.float32)  # second row invalid
        cfg = TensorsConfig(TensorsInfo.from_strings(
            "4:2:1,2:1,2:1,1", "float32"))

        def make():
            d = find_decoder("bounding_box")()
            d.init({1: "mobilenet-ssd-postprocess", 4: "64:64", 5: "64:64"})
            return d

        host_out, fused_out = self._fused_roundtrip(
            make, (boxes, classes, scores, count), cfg)
        assert len(host_out.meta["detections"]) == 1
        self._same_detections(host_out, fused_out)

    def test_ov_mode(self):
        from nnstreamer_tpu.decoders.base import find_decoder

        rng = np.random.default_rng(10)
        rows = np.zeros((1, 8, 7), np.float32)
        rows[0, :, 0] = [0, 0, 0, -1, 0, 0, -1, 0]  # two invalid markers
        rows[0, :, 1] = rng.integers(0, 4, 8)
        rows[0, :, 2] = rng.uniform(0.3, 1.0, 8)
        rows[0, :, 3:] = np.sort(
            rng.uniform(0, 1, (8, 4)).astype(np.float32), axis=1)
        cfg = TensorsConfig(TensorsInfo.from_strings("7:8:1", "float32"))

        def make():
            d = find_decoder("bounding_box")()
            d.init({1: "ov-person-detection", 4: "64:64", 5: "64:64"})
            return d

        host_out, fused_out = self._fused_roundtrip(make, (rows,), cfg)
        self._same_detections(host_out, fused_out)

    def test_snpe_deeplab_pre_argmaxed(self):
        from nnstreamer_tpu.decoders.base import find_decoder

        rng = np.random.default_rng(11)
        ids = rng.integers(0, 21, (1, 9, 7)).astype(np.float32)
        cfg = TensorsConfig(TensorsInfo.from_strings("7:9:1", "float32"))

        def make():
            d = find_decoder("image_segment")()
            d.init({1: "snpe-deeplab"})
            return d

        host_out, fused_out = self._fused_roundtrip(make, (ids,), cfg)
        np.testing.assert_array_equal(host_out.memories[0].host(),
                                      fused_out.memories[0].host())

    def test_snpe_depth_has_no_reduce(self):
        from nnstreamer_tpu.decoders.base import find_decoder

        d = find_decoder("image_segment")()
        d.init({1: "snpe-depth"})
        # data-dependent min/max normalize: host-only, never fused
        assert d.epilogue_reduce() is None


# --------------------------------------------------------------------------- #
# filter-level coalescing + sched composition
# --------------------------------------------------------------------------- #

class TestCoalesce:
    SPEC = ("zoo://mobilenet_v2?width=0.25&size=32&num_classes=16"
            "&dtype=float32")

    def test_epilogue_token_splits_and_joins_coalesce_key(self):
        from nnstreamer_tpu.filters.base import FilterProps
        from nnstreamer_tpu.filters.xla import XLAFilter
        from nnstreamer_tpu.sched.engine import _coalesce_key

        mem = TensorMemory(np.zeros((1, 32, 32, 3), np.float32))
        a, b, c = XLAFilter(), XLAFilter(), XLAFilter()
        for f in (a, b, c):
            f.open(FilterProps(model=self.SPEC))
        try:
            base = a.invoke([mem])[0].host()

            def post(outs):
                return tuple(y * 2.0 for y in outs)

            a.set_fused_epilogue(post, token="t1")
            b.set_fused_epilogue(post, token="t1")
            c.set_fused_epilogue(post, token="t2")
            assert _coalesce_key(a, [mem]) == _coalesce_key(b, [mem])
            assert _coalesce_key(c, [mem]) != _coalesce_key(a, [mem])
            np.testing.assert_allclose(a.invoke([mem])[0].host(), base * 2.0,
                                       rtol=1e-6)
        finally:
            for f in (a, b, c):
                f.close()

    def test_sched_composed_coalesced_epilogue(self):
        from nnstreamer_tpu.models.zoo import ModelBundle
        from nnstreamer_tpu.sched import DeviceEngine

        # one shared bundle: the coalesce token anchors on bundle
        # identity, so both filters must resolve to the same object
        model = ModelBundle(
            "epi_mean",
            lambda x: jnp.asarray(x, jnp.float32).mean(axis=(1, 2, 3)))

        def build(n, scheduler=None, auto_fuse=True):
            p = Pipeline(f"epi{n}", scheduler=scheduler)
            p.auto_fuse = auto_fuse
            src = p.add_new("videotestsrc", width=16, height=16,
                            num_buffers=3, pattern="random", seed=50 + n)
            conv = p.add_new("tensor_converter")
            filt = p.add_new("tensor_filter", framework="xla-tpu",
                             model=model)
            tr = p.add_new("tensor_transform", mode="arithmetic",
                           option="mul:2.0,add:1.0")
            sink = p.add_new("tensor_sink", store=True)
            Pipeline.link(src, conv, filt, tr, sink)
            return p, filt, sink

        def outputs(sink):
            return [np.asarray(b.memories[0].host()) for b in sink.buffers]

        # serial, unfused: the oracle
        serial = []
        for i in range(2):
            p, _, sink = build(i, auto_fuse=False)
            p.run(timeout=120)
            serial.append(outputs(sink))

        eng = DeviceEngine("epi", autostart=True, max_coalesce=4)
        try:
            built = [build(i, scheduler=eng) for i in range(2)]
            for p, _, _ in built:
                p.start()
            for p, _, _ in built:
                assert p.wait_eos(120)
            tokens = [f.fw.coalesce_token for _, f, _ in built]
            assert tokens[0] == tokens[1]
            assert any(isinstance(part, tuple) and len(part) == 2
                       and part[0] == "post" for part in tokens[0])
            for p, _, _ in built:
                assert p._epilogue_count == 1
                p.stop()
            assert eng.stats["items"] == 2 * 3
            for i, (_, _, sink) in enumerate(built):
                got = outputs(sink)
                assert len(got) == len(serial[i]) == 3
                for a, b in zip(got, serial[i]):
                    np.testing.assert_array_equal(a, b)
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# profiler-driven selection
# --------------------------------------------------------------------------- #

class TestProfilerSelect:
    def test_no_samples_fuses_unconditionally(self):
        from nnstreamer_tpu.obs.profile import Profiler

        p = Profiler()
        assert p.epilogue_select("f0", ["t0", "d0"]) is True

    def test_cheap_chain_declined_costly_chain_fused(self):
        from nnstreamer_tpu.obs.profile import Profiler

        p = Profiler()
        for dur in (200, 300):
            p._records.append({"kind": "element", "label": "t0",
                               "dur_ns": dur})
        assert p.epilogue_select("f0", ["t0"]) is False
        p._records.append({"kind": "element", "label": "d0",
                           "dur_ns": 50_000})
        assert p.epilogue_select("f0", ["t0", "d0"]) is True

    def test_enable_installs_select_hook(self):
        from nnstreamer_tpu.obs import profile as prof
        from nnstreamer_tpu.ops import epilogue as epi

        prior = epi.EPILOGUE_SELECT_HOOK
        prof.enable()
        try:
            assert epi.EPILOGUE_SELECT_HOOK is not None
            assert epi.EPILOGUE_SELECT_HOOK == prof.profiler().epilogue_select
        finally:
            prof.disable()
        assert epi.EPILOGUE_SELECT_HOOK is None
        assert prior is None or True  # restored to cleared state

    def test_fused_dispatch_label_carries_epilogue_token(self):
        from nnstreamer_tpu.obs import profile as prof

        data = [np.ones((1, 4), np.float32)]
        prof.enable()
        try:
            prof.profiler().reset()
            p = Pipeline()
            src = p.add_new("appsrc", caps=caps_of("4:1", "float32"),
                            data=data)
            f = p.add_new("tensor_filter", model=lambda x: x + 1)
            t = p.add_new("tensor_transform", mode="typecast",
                          option="float32")
            sink = p.add_new("tensor_sink", store=True)
            Pipeline.link(src, f, t, sink)
            p.run(timeout=60)
            assert p._epilogue_count == 1
            labels = [r["label"]
                      for r in prof.profiler().records(kind="dispatch")]
            assert any("+post[" in lb for lb in labels), labels
        finally:
            prof.disable()
