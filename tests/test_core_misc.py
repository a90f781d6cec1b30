"""Buffer, meta header, typed data, registry, config tests."""

import numpy as np
import pytest

from nnstreamer_tpu.core import (
    Buffer,
    META_SIZE,
    SubpluginType,
    TensorDType,
    TensorFormat,
    TensorInfo,
    TensorMemory,
    TensorMetaInfo,
    get_all_subplugins,
    get_subplugin,
    register_subplugin,
    unregister_subplugin,
    unwrap_flex,
    wrap_flex,
)
from nnstreamer_tpu.core import data as tdata
from nnstreamer_tpu.core.config import reset_config
from nnstreamer_tpu.core.hw import AcceleratorSpec


class TestTensorMemory:
    def test_host_roundtrip(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        m = TensorMemory(a)
        assert m.info.shape == (3, 4)
        assert m.info.dims == (4, 3)
        np.testing.assert_array_equal(m.host(), a)

    def test_device_lazy(self):
        import jax

        m = TensorMemory(np.ones((2, 2), np.float32))
        assert not m.is_device
        d = m.device()
        assert isinstance(d, jax.Array)
        np.testing.assert_array_equal(np.asarray(d), m.host())

    def test_from_device(self):
        import jax.numpy as jnp

        m = TensorMemory(jnp.zeros((5,), jnp.int32))
        assert m.is_device
        assert m.host().shape == (5,)

    def test_bytes_roundtrip(self):
        a = np.arange(6, dtype=np.uint16).reshape(2, 3)
        m = TensorMemory(a)
        m2 = TensorMemory.from_bytes(m.tobytes(), m.info)
        np.testing.assert_array_equal(m2.host(), a)


class TestBuffer:
    def test_of(self):
        b = Buffer.of(np.zeros((2, 2)), np.ones(3), pts=1000)
        assert b.num_tensors == 2
        assert b.pts == 1000

    def test_with_memories_keeps_timestamps(self):
        b = Buffer.of(np.zeros(4), pts=5, duration=7, offset=2)
        b2 = b.with_memories([TensorMemory(np.ones(2))])
        assert (b2.pts, b2.duration, b2.offset) == (5, 7, 2)
        assert b2.num_tensors == 1


class TestMeta:
    def test_pack_parse(self):
        info = TensorInfo.from_strings("3:224:224", "uint8")
        meta = TensorMetaInfo(info, TensorFormat.FLEXIBLE, "video/x-raw")
        raw = meta.pack()
        assert len(raw) == META_SIZE
        meta2 = TensorMetaInfo.parse(raw)
        assert meta2.info.dims == info.dims
        assert meta2.info.dtype is TensorDType.UINT8
        assert meta2.format is TensorFormat.FLEXIBLE
        assert meta2.media_type == "video/x-raw"

    def test_wrap_unwrap(self):
        info = TensorInfo.from_strings("4", "float32")
        payload = np.arange(4, dtype=np.float32).tobytes()
        blob = wrap_flex(payload, info)
        meta, out = unwrap_flex(blob)
        assert out == payload
        assert meta.info.is_compatible(info)

    def test_truncated(self):
        with pytest.raises(ValueError):
            TensorMetaInfo.parse(b"\x00" * 10)

    def test_bad_magic(self):
        with pytest.raises(ValueError):
            TensorMetaInfo.parse(b"\xff" * META_SIZE)


class TestTypedData:
    def test_typecast_saturation_semantics(self):
        # C-style modular wrap for ints (reference gst_tensor_data_typecast)
        assert tdata.typecast_value(300, TensorDType.UINT8) == 300 % 256

    def test_typecast_float_to_int(self):
        assert tdata.typecast_value(3.9, TensorDType.INT32) == 3

    def test_average_std(self):
        a = np.array([1, 2, 3, 4], np.float32)
        assert tdata.tensor_average(a) == 2.5
        assert tdata.tensor_std(a) == pytest.approx(np.std(a))

    def test_per_channel(self):
        a = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        avg = tdata.per_channel_average(a, channel_axis=-1)
        assert avg.shape == (4,)
        np.testing.assert_allclose(avg, a.reshape(-1, 4).mean(axis=0))


class TestRegistry:
    def test_register_lookup(self):
        assert register_subplugin(SubpluginType.DECODER, "TeStDec", object())
        assert get_subplugin(SubpluginType.DECODER, "testdec") is not None
        assert "testdec" in get_all_subplugins(SubpluginType.DECODER)
        assert unregister_subplugin(SubpluginType.DECODER, "testdec")

    def test_duplicate_fails(self):
        register_subplugin(SubpluginType.DECODER, "dup", 1)
        try:
            assert not register_subplugin(SubpluginType.DECODER, "dup", 2)
            assert register_subplugin(SubpluginType.DECODER, "dup", 2, replace=True)
        finally:
            unregister_subplugin(SubpluginType.DECODER, "dup")

    def test_miss(self):
        assert get_subplugin(SubpluginType.CONVERTER, "nope-nothing") is None


class TestConfig:
    def test_ini_and_env(self, tmp_path, monkeypatch):
        ini = tmp_path / "t.ini"
        ini.write_text(
            "[common]\nenable_envvar=true\n"
            "[filter]\nframework_priority_tflite=xla-tpu,python3\n"
            "[xla-tpu]\nprecision=bf16\n")
        cfg = reset_config(str(ini))
        assert cfg.framework_priority(".tflite") == ["xla-tpu", "python3"]
        assert cfg.framework_priority("py") == ["python3"]  # default table
        assert cfg.get_custom_value("xla-tpu", "precision") == "bf16"
        monkeypatch.setenv("NNS_TPU_XLA_TPU_PRECISION", "f32")
        assert cfg.get_custom_value("xla-tpu", "precision") == "f32"
        reset_config()


class TestAccelerator:
    def test_parse(self):
        s = AcceleratorSpec.parse("true:tpu,cpu")
        assert s.enabled and s.preference == ("tpu", "cpu")
        assert not AcceleratorSpec.parse("false").enabled
        assert AcceleratorSpec.parse(None).enabled

    def test_unavailable_preference_says_so(self, caplog):
        """A stated preference that is not there still resolves (reference
        parse_accl_hw semantics) — with a warning naming where it landed."""
        import logging

        with caplog.at_level(logging.WARNING):
            dev = AcceleratorSpec.parse("true:tpu").pick_device()
        assert dev.platform == "cpu"
        assert any("tpu not available" in r.getMessage()
                   for r in caplog.records)

    def test_on_tpu_asks_the_device(self):
        import jax
        from types import SimpleNamespace

        from nnstreamer_tpu.core.hw import on_tpu

        assert not on_tpu(jax.devices()[0])
        assert on_tpu(SimpleNamespace(platform="tpu"))


class TestCompileCache:
    """core/hw.enable_compile_cache: placed from outside or at one fixed
    path — the path is part of the cache key, so it must never move."""

    @pytest.fixture
    def restore(self):
        import jax

        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_placement_is_left_alone(self, monkeypatch, restore):
        import jax

        from nnstreamer_tpu.core.hw import enable_compile_cache

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert enable_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir is None  # untouched

    def test_default_is_fixed_path_in_checkout(self, monkeypatch, restore):
        import os

        import jax

        import nnstreamer_tpu
        from nnstreamer_tpu.core.hw import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(nnstreamer_tpu.__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert enable_compile_cache() == want  # same path every call


def test_chip_smoke_refuses_a_cpu():
    """`JAX_PLATFORMS=cpu python chip_smoke.py` must fail fast — before
    compiling anything — and print no result line."""
    import os
    import subprocess
    import sys
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""  # no JSON, no leg ran
    assert "no TPU" in out.stderr
    assert time.monotonic() - t0 < 60
