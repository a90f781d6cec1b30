"""Full BASELINE-config pipelines with the native model zoo:
SSD→bounding_box, DeepLab→image_segment, PoseNet→pose, LSTM repo loop
(mirrors BASELINE.md's five configs on tiny shapes)."""

import numpy as np
import pytest

from nnstreamer_tpu.graph import Pipeline
from nnstreamer_tpu.models.zoo import get_model, model_names


def test_zoo_catalog_complete():
    names = model_names()
    for required in ["mobilenet_v1", "mobilenet_v2", "ssd_mobilenet_v2", "deeplab_v3",
                     "posenet", "lstm_cell", "lenet", "mnist", "causal_lm",
                     "moe_transformer", "stream_transformer",
                     "passthrough", "scaler"]:
        assert required in names


def test_ssd_detection_pipeline_with_priors(tmp_path):
    from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

    priors = tmp_path / "box_priors.txt"
    n = write_box_priors(str(priors), size=96)
    labels = tmp_path / "labels.txt"
    labels.write_text("\n".join(f"c{i}" for i in range(6)))
    bundle = get_model("zoo://ssd_mobilenet_v2?size=96&width=0.25"
                       "&num_classes=6&dtype=float32")
    assert bundle.metadata["anchors"] == n
    p = Pipeline()
    src = p.add_new("videotestsrc", width=96, height=96, num_buffers=2,
                    pattern="random")
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", framework="xla-tpu", model=bundle)
    dec = p.add_new("tensor_decoder", mode="bounding_box",
                    option1="mobilenet-ssd", option2=str(labels),
                    option3=str(priors), option4="96:96", option5="96:96")
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=180)
    assert sink.num_buffers == 2
    b = sink.buffers[0]
    assert b.memories[0].host().shape == (96, 96, 4)
    assert isinstance(b.meta["detections"], list)  # untrained → any count


def test_deeplab_segmentation_pipeline():
    bundle = get_model("zoo://deeplab_v3?size=33&width=0.25&num_classes=5"
                       "&dtype=float32")
    p = Pipeline()
    src = p.add_new("videotestsrc", width=33, height=33, num_buffers=2)
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", model=bundle)
    dec = p.add_new("tensor_decoder", mode="image_segment",
                    option1="tflite-deeplab")
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=180)
    mask = sink.buffers[0].memories[0].host()
    assert mask.shape == (33, 33, 4)


def test_posenet_pipeline():
    bundle = get_model("zoo://posenet?size=33&width=0.25&dtype=float32")
    p = Pipeline()
    src = p.add_new("videotestsrc", width=33, height=33, num_buffers=1)
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", model=bundle)
    dec = p.add_new("tensor_decoder", mode="pose_estimation",
                    option1="66:66", option2="33:33", option4="heatmap-offset")
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=180)
    b = sink.buffers[0]
    assert len(b.meta["keypoints"]) == 17
    assert b.memories[0].host().shape == (66, 66, 4)


def test_lstm_repo_loop_with_zoo_cell():
    """Composite config: mux + repo loop driving the flax LSTM cell."""
    from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
    from nnstreamer_tpu.elements.repo import reset_repo

    reset_repo()
    bundle = get_model("zoo://lstm_cell?features=8&input_size=4")
    p = Pipeline()
    xs = [np.random.default_rng(i).normal(size=(1, 4)).astype(np.float32)
          for i in range(3)]
    src = p.add_new("appsrc",
                    caps=Caps.tensors(TensorsConfig(
                        TensorsInfo.from_strings("4:1", "float32"), 30)),
                    data=xs)
    state = p.add_new("tensor_reposrc", slot_index=9, dims="8:1,8:1",
                      types="float32,float32")
    mux = p.add_new("tensor_mux", sync_mode="nosync")
    filt = p.add_new("tensor_filter", model=bundle)
    demux = p.add_new("tensor_demux", tensorpick="0,1:2")
    qo = p.add_new("queue")
    qs = p.add_new("queue")
    out_sink = p.add_new("tensor_sink", store=True)
    repo_sink = p.add_new("tensor_reposink", slot_index=9)
    Pipeline.link(src, mux)
    Pipeline.link(state, mux)
    Pipeline.link(mux, filt, demux)
    Pipeline.link(demux, qo, out_sink)   # y
    Pipeline.link(demux, qs, repo_sink)  # (h', c') back into the loop
    p.start()
    import time

    deadline = time.monotonic() + 60
    while out_sink.num_buffers < 3 and time.monotonic() < deadline:
        time.sleep(0.05)
    p.stop()
    assert out_sink.num_buffers >= 3
    # recurrent state actually evolved: same input at t0/t1 would give
    # different outputs; verify outputs finite and not identical
    y0 = out_sink.buffers[0].memories[0].host()
    y1 = out_sink.buffers[1].memories[0].host()
    assert np.all(np.isfinite(y0)) and np.all(np.isfinite(y1))
    assert not np.array_equal(y0, y1)


class TestStreamTransformer:
    def test_single_device_forward(self):
        bundle = get_model("zoo://stream_transformer?layers=1&dim=32&heads=4"
                           "&seq=16&dtype=float32")
        import jax

        out = jax.jit(bundle.fn())(np.zeros((1, 16, 32), np.float32))
        assert out.shape == (1, 16, 32)

    def test_sequence_parallel_matches_single_device(self):
        import jax
        import jax.numpy as jnp
        from nnstreamer_tpu.models.stream_transformer import make_sp_apply
        from nnstreamer_tpu.parallel import make_mesh

        bundle = get_model("zoo://stream_transformer?layers=1&dim=32&heads=8"
                           "&seq=64&dtype=float32")
        x = np.random.default_rng(0).normal(size=(1, 64, 32)).astype(np.float32)
        ref = np.asarray(bundle.fn()(jnp.asarray(x)))
        mesh = make_mesh({"sp": 8})
        for mode in ("ring", "a2a"):
            apply_sp, params = make_sp_apply(bundle, mesh, mode=mode)
            out = np.asarray(apply_sp(params, jnp.asarray(x)))
            np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-4)

    def test_in_pipeline_with_aggregator(self):
        """Streaming use: per-frame embeddings → aggregator window →
        transformer filter (the long-context streaming pattern)."""
        from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
        from nnstreamer_tpu.graph import Pipeline

        bundle = get_model("zoo://stream_transformer?layers=1&dim=16&heads=2"
                           "&seq=4&dtype=float32")
        p = Pipeline()
        src = p.add_new("appsrc",
                        caps=Caps.tensors(TensorsConfig(
                            TensorsInfo.from_strings("16:1:1", "float32"), 30)),
                        data=[np.full((1, 1, 16), i, np.float32)
                              for i in range(8)])
        agg = p.add_new("tensor_aggregator", frames_out=4, frames_dim=1)
        filt = p.add_new("tensor_filter", model=bundle)
        sink = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, agg, filt, sink)
        p.run(timeout=120)
        assert sink.num_buffers == 2
        assert sink.buffers[0].memories[0].host().shape == (1, 4, 16)


def test_bounding_box_device_reduce_matches_host(tmp_path):
    """submit/complete (device top-K reduce) must yield the same detections
    as the plain host decode path."""
    import jax
    import numpy as np
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
    from nnstreamer_tpu.decoders.base import find_decoder
    from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

    priors = tmp_path / "p.txt"
    n = write_box_priors(str(priors), size=96)
    labels = tmp_path / "l.txt"
    labels.write_text("\n".join(f"c{i}" for i in range(6)))
    rng = np.random.default_rng(3)
    locs = rng.normal(size=(1, n, 4)).astype(np.float32)
    raw = rng.normal(size=(1, n, 6)).astype(np.float32) * 4  # some pass 0.5

    def make():
        d = find_decoder("bounding_box")()
        d.init({1: "mobilenet-ssd", 2: str(labels), 3: str(priors),
                4: "96:96", 5: "96:96"})
        return d

    cfg = TensorsConfig(TensorsInfo.from_strings(
        f"4:{n}:1,6:{n}:1", "float32,float32"))
    host_out = make().decode(Buffer.of(locs, raw), cfg)
    dev = make()
    buf_dev = Buffer.of(jax.device_put(locs), jax.device_put(raw))
    token = dev.submit(buf_dev, cfg)
    assert isinstance(token, tuple), "device reduce path not taken"
    dev_out = dev.complete(token, cfg)
    h = host_out.meta["detections"]
    d = dev_out.meta["detections"]
    # both paths apply the same PRE_NMS_TOPK cap + NMS: identical results
    assert len(d) > 0 and len(h) == len(d)
    for a, b in zip(h, d):
        assert a["class"] == b["class"]
        np.testing.assert_allclose(a["box"], b["box"], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(a["score"], b["score"], rtol=1e-4)
    np.testing.assert_array_equal(host_out.memories[0].host().shape,
                                  dev_out.memories[0].host().shape)


def test_image_segment_device_reduce_matches_host():
    import jax
    import numpy as np
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
    from nnstreamer_tpu.decoders.base import find_decoder

    rng = np.random.default_rng(0)
    seg = rng.normal(size=(1, 17, 19, 5)).astype(np.float32)
    cfg = TensorsConfig(TensorsInfo.from_strings("5:19:17:1", "float32"))

    def make():
        d = find_decoder("image_segment")()
        d.init({1: "tflite-deeplab"})
        return d

    host_out = make().decode(Buffer.of(seg), cfg)
    dev = make()
    token = dev.submit(Buffer.of(jax.device_put(seg)), cfg)
    assert isinstance(token, tuple)
    dev_out = dev.complete(token, cfg)
    np.testing.assert_array_equal(host_out.memories[0].host(),
                                  dev_out.memories[0].host())


def test_pose_device_reduce_matches_host():
    import jax
    import numpy as np
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
    from nnstreamer_tpu.decoders.base import find_decoder

    rng = np.random.default_rng(1)
    hm = rng.normal(size=(1, 9, 11, 17)).astype(np.float32)
    off = rng.normal(size=(1, 9, 11, 34)).astype(np.float32)
    cfg = TensorsConfig(TensorsInfo.from_strings(
        "17:11:9:1,34:11:9:1", "float32,float32"))

    def make():
        d = find_decoder("pose_estimation")()
        d.init({1: "66:66", 2: "33:33", 4: "heatmap-offset"})
        return d

    host_out = make().decode(Buffer.of(hm, off), cfg)
    dev = make()
    token = dev.submit(Buffer.of(jax.device_put(hm), jax.device_put(off)), cfg)
    assert isinstance(token, tuple)
    dev_out = dev.complete(token, cfg)
    np.testing.assert_allclose(host_out.meta["keypoints"],
                               dev_out.meta["keypoints"], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(host_out.memories[0].host(),
                                  dev_out.memories[0].host())

def test_bounding_box_device_reduce_overflow_candidates(tmp_path):
    """When more anchors pass the threshold than PRE_NMS_TOPK (untrained
    models emit ~0.5 sigmoid scores everywhere), both paths must cap at the
    same top-K candidate set and still agree — the round-2 host fallback
    that shipped full logits D2H every frame is gone by design."""
    import jax
    import numpy as np
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
    from nnstreamer_tpu.decoders.base import find_decoder
    from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

    priors = tmp_path / "p.txt"
    n = write_box_priors(str(priors), size=192)
    assert n > 256, "need more anchors than the cap for this test"
    rng = np.random.default_rng(7)
    locs = rng.normal(size=(1, n, 4)).astype(np.float32)
    raw = np.abs(rng.normal(size=(1, n, 6))).astype(np.float32)  # all >= 0.5

    def make():
        d = find_decoder("bounding_box")()
        d.init({1: "mobilenet-ssd", 3: str(priors), 4: "192:192",
                5: "192:192"})
        return d

    cfg = TensorsConfig(TensorsInfo.from_strings(
        f"4:{n}:1,6:{n}:1", "float32,float32"))
    host_out = make().decode(Buffer.of(locs, raw), cfg)
    dev = make()
    token = dev.submit(
        Buffer.of(jax.device_put(locs), jax.device_put(raw)), cfg)
    assert isinstance(token, tuple), "device reduce path not taken"
    # the shipped reduction is K rows of 6 floats — nowhere near the
    # n*(4+classes) logits the old fallback pulled back
    assert token[1].host().nbytes <= dev.PRE_NMS_TOPK * 6 * 4
    dev_out = dev.complete(token, cfg)
    h, d = host_out.meta["detections"], dev_out.meta["detections"]
    assert len(h) == len(d) > 0
    for a, b in zip(h, d):
        assert a["class"] == b["class"]
        np.testing.assert_allclose(a["box"], b["box"], rtol=1e-4, atol=1e-5)


def test_batched_serving_frames_per_tensor(tmp_path):
    """Micro-batched serving (VERDICT r2 #4): converter frames-per-tensor
    regroups N frames into one (N,...) tensor, the model runs batch=N on
    one invoke, and image_labeling emits one label per frame."""
    labels = tmp_path / "l.txt"
    labels.write_text("\n".join(f"c{i}" for i in range(7)))
    batch = 4
    p = Pipeline()
    src = p.add_new("videotestsrc", width=32, height=32,
                    num_buffers=3 * batch, pattern="random")
    conv = p.add_new("tensor_converter", frames_per_tensor=batch)
    filt = p.add_new("tensor_filter", framework="xla-tpu",
                     model="zoo://mobilenet_v2?width=0.25&size=32"
                           f"&num_classes=7&dtype=float32&batch={batch}")
    dec = p.add_new("tensor_decoder", mode="image_labeling",
                    option1=str(labels), async_depth=2)
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=180)
    assert sink.num_buffers == 3
    for b in sink.buffers:
        assert len(b.meta["labels"]) == batch
        assert len(b.meta["label_scores"]) == batch


def test_get_model_memoizes_pure_specs(tmp_path):
    from nnstreamer_tpu.models.zoo import get_model

    a = get_model("zoo://scaler?dims=4:1&types=float32&scale=2")
    b = get_model("zoo://scaler?dims=4:1&types=float32&scale=2")
    assert a is b
    c = get_model("zoo://scaler?dims=4:1&types=float32&scale=3")
    assert c is not a


def test_filter_only_options_do_not_fork_bundles():
    """custom= options the filter consumes (sync/precision/donate/...) must
    not leak into model resolution — a latency (sync=true) and a
    throughput pipeline over the same spec share one bundle and one jit."""
    from nnstreamer_tpu.filters.base import FilterProps
    from nnstreamer_tpu.filters.xla import XLAFilter, resolve_model

    a = resolve_model("zoo://scaler?dims=4:1&types=float32&scale=2",
                      {"sync": "true"})
    b = resolve_model("zoo://scaler?dims=4:1&types=float32&scale=2", {})
    assert a is b
    fa, fb = XLAFilter(), XLAFilter()
    fa.open(FilterProps(model="zoo://scaler?dims=4:1&types=float32&scale=2",
                        custom="sync=true"))
    fb.open(FilterProps(model="zoo://scaler?dims=4:1&types=float32&scale=2"))
    assert fa._jitted is fb._jitted, "jit not shared across filters"


def test_get_model_non_string_override_still_resolves():
    """Non-str overrides (programmatic callers) bypass the memo without
    crashing on key construction."""
    from nnstreamer_tpu.models.zoo import get_model

    a = get_model("zoo://scaler?dims=4:1&types=float32", scale=2.5)
    b = get_model("zoo://scaler?dims=4:1&types=float32", scale=2.5)
    assert a is not b  # float override -> uncacheable -> fresh bundle


def test_lenet_mnist_pipeline(tmp_path):
    """GRAY8 stream → zoo://lenet → image_labeling (the reference's
    mnist.pb classification pipeline shape, tests/test_models parity)."""
    from fractions import Fraction

    from nnstreamer_tpu.core import Caps

    labels = tmp_path / "digits.txt"
    labels.write_text("\n".join(str(i) for i in range(10)))
    p = Pipeline()
    frames = [np.random.default_rng(i).integers(0, 255, (28, 28, 1))
              .astype(np.uint8) for i in range(3)]
    src = p.add_new("appsrc", caps=Caps("video/x-raw", {
        "format": "GRAY8", "width": 28, "height": 28,
        "framerate": Fraction(0, 1)}), data=frames)
    conv = p.add_new("tensor_converter")
    filt = p.add_new("tensor_filter", framework="xla-tpu",
                     model="zoo://lenet")
    dec = p.add_new("tensor_decoder", mode="image_labeling",
                    option1=str(labels))
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, conv, filt, dec, sink)
    p.run(timeout=120)
    assert sink.num_buffers == 3
    assert sink.buffers[0].meta["label"] in [str(i) for i in range(10)]


def test_lenet_exports_and_redeploys(tmp_path):
    from nnstreamer_tpu.models import export_model, get_model, load_exported

    bundle = get_model("zoo://mnist")
    assert bundle is get_model("zoo://lenet")  # alias shares the memo entry
    path = str(tmp_path / "mnist.jaxexport")
    export_model(path, bundle)
    back = load_exported(path)
    x = np.random.default_rng(0).integers(0, 255, (1, 28, 28, 1)).astype(np.uint8)
    np.testing.assert_allclose(
        np.asarray(bundle.fn()(x)), np.asarray(back.fn()(x)[0]),
        rtol=1e-5, atol=1e-6)


def test_user_factory_beats_builtin_alias():
    """register_model under an aliased name must win over the alias (user
    extension point; silent shadowing would swap in the wrong model)."""
    from nnstreamer_tpu.models.zoo import (
        _aliases, _factories, get_model, register_alias, register_model)
    from nnstreamer_tpu.models.zoo import ModelBundle

    import pytest

    marker = ModelBundle("user_mnist", lambda x: x)
    register_model("mnist", lambda **_: marker)
    try:
        assert get_model("zoo://mnist") is marker
        with pytest.raises(ValueError, match="unknown canonical"):
            register_alias("foo", "no_such_model")
    finally:
        # restore the builtin alias
        _factories.pop("mnist", None)
        register_alias("mnist", "lenet")


class TestMobileNetV1:
    """The reference's flagship test model (mobilenet_v1 quant tflite):
    native v1 + quant=w8 mirrors the quantized serving shape."""

    def test_forward_shapes_and_param_count(self):
        import jax

        b = get_model("zoo://mobilenet_v1?width=0.25&size=32&num_classes=16"
                      "&dtype=float32")
        x = np.random.default_rng(0).integers(
            0, 255, (1, 32, 32, 3)).astype(np.uint8)
        out = jax.jit(b.fn())(x)
        assert out.shape == (1, 16)
        assert np.isfinite(np.asarray(out)).all()
        # v1@0.25 must be a different (smaller) network than v2@0.25
        v2 = get_model("zoo://mobilenet_v2?width=0.25&size=32"
                       "&num_classes=16&dtype=float32")
        n1 = sum(np.asarray(p).size
                 for p in jax.tree_util.tree_leaves(b.params))
        n2 = sum(np.asarray(p).size
                 for p in jax.tree_util.tree_leaves(v2.params))
        assert n1 != n2

    def test_quantized_label_pipeline(self, tmp_path):
        labels = tmp_path / "l.txt"
        labels.write_text("\n".join(f"c{i}" for i in range(16)))
        p = Pipeline()
        src = p.add_new("videotestsrc", width=32, height=32, num_buffers=3,
                        pattern="random")
        conv = p.add_new("tensor_converter")
        filt = p.add_new("tensor_filter", framework="xla-tpu",
                         model="zoo://mobilenet_v1?width=0.25&size=32"
                               "&num_classes=16&dtype=float32",
                         custom="quant=w8")
        dec = p.add_new("tensor_decoder", mode="image_labeling",
                        option1=str(labels))
        sink = p.add_new("tensor_sink", store=True)
        Pipeline.link(src, conv, filt, dec, sink)
        p.run(timeout=180)
        assert sink.num_buffers == 3
        assert sink.buffers[0].meta["label"].startswith("c")
