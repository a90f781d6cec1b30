"""The two kernels of the latent-attention / expert family compiled for a
described v5e chip at GLM-4.7-Flash's widths, the GPT-2 block's decode
attention at Cerebras-GPT-1.3B's, and the Gated DeltaNet family's
recurrent update and grouped decode attention at Qwen3-Next-80B-A3B's, by
the TPU compiler that is installed beside JAX: Mosaic's own passes run (they refused a one-row DMA
into a 512-wide latent row that every interpreter test had passed), nothing
executes, no chip is needed. One file, one worker: the process that
describes the topology holds libtpu until it exits (the topology is
described inside a fixture, never at import)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_latent_decode_attention_compiles_at_the_published_widths(
        one_chip, no_cache):
    from nnstreamer_tpu.ops.pallas import latent_attention as la

    s, h, c, r, layers, m = 32, 20, 512, 64, 5, 2048
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(functools.partial(la._latent_pallas, sm_scale=1 / 16,
                                   block=256, interpret=False),
                 donate_argnums=(4, 5))
    compiled = fn.lower(
        sd((s, h, c)), sd((s, h, r)), sd((s, c)), sd((s, r)),
        sd((s, layers, c // 128, m, 128)), sd((s, layers, m, 128)),
        sd((), jnp.int32), sd((s,), jnp.int32), sd((s,), jnp.bool_)
    ).compile()
    mem = compiled.memory_analysis()
    # the stores are updated in place: nothing of their size is a temporary
    assert mem.alias_size_in_bytes >= s * layers * m * (c + 128) * 4
    assert mem.temp_size_in_bytes < (8 << 20)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [32, 96])
def test_expert_gemm_compiles_at_the_published_widths(one_chip, no_cache,
                                                      rows):
    from nnstreamer_tpu.ops.pallas import moe_gemm as mg

    d, f, e, k = 2048, 1536, 64, 4
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(functools.partial(mg._gemm_pallas, k=k,
                                   block=mg.hidden_block(f),
                                   interpret=False))
    compiled = fn.lower(
        sd((rows, d)), sd((rows, e)), sd((rows, e), jnp.bool_),
        sd((e, d, f)), sd((e, d, f)), sd((e, f, d))).compile()
    # the experts' 2.4 GB are read where they lie: no copy of them is made
    assert compiled.memory_analysis().temp_size_in_bytes < (8 << 20)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_at_the_production_shape(one_chip,
                                                           no_cache):
    """A stream's tail, sub-tile DMAs of a static shape into a strided
    window of the buffer issued by a ``pl.loop`` whose count follows
    ``pos``, and the loop that clears the tail's value rows at or past
    ``pos``, pass Mosaic: 8 slots, 24 layers x 16 heads, 2048 x 128,
    float32."""
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    s, layers, h, m, hd = 8, 24, 16, 2048, 128
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    block = da.kv_block(m)
    fn = jax.jit(functools.partial(da._decode_pallas, layer_axis=1,
                                   block=block, grain=da.read_grain(m),
                                   interpret=False), donate_argnums=(3, 4))
    row = sd((s, h, 1, hd))
    compiled = fn.lower(
        row, row, row, sd((s, layers, h, m, hd)), sd((s, layers, h, m, hd)),
        sd((), jnp.int32), sd((s,), jnp.int32), sd((s,), jnp.bool_)
    ).compile()
    # the stores are updated in place
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * s * layers * h * m * hd * 4
    assert "tpu_custom_call" in compiled.as_text()


def test_gated_delta_compiles_at_the_published_widths(one_chip, no_cache):
    """64 slots x 6 Gated DeltaNet layers x 32 value heads of 128 x 128
    (16 key heads): the state is updated in place, a stream's 32 states a
    DMA into a buffer of three."""
    from nnstreamer_tpu.ops.pallas import gated_delta as gd

    s, layers, h, hk, d = 64, 6, 32, 16, 128
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(functools.partial(gd._step_pallas, interpret=False),
                 donate_argnums=(5,))
    compiled = fn.lower(
        sd((s, hk, d)), sd((s, hk, d)), sd((s, h, d)), sd((s, h)),
        sd((s, h)), sd((s, layers, h, d, d)), sd((), jnp.int32),
        sd((s,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= s * layers * h * d * d * 4
    assert mem.temp_size_in_bytes < (8 << 20)
    assert "tpu_custom_call" in compiled.as_text()


def test_grouped_decode_attention_compiles_at_the_published_widths(
        one_chip, no_cache):
    """16 query heads over 2 K/V heads of 256, 64 slots x 2 full layers x
    2048 positions: an item reads a K/V head's block once for its group of
    8, and a new row 256 wide goes in through its tile of 8 rows (Mosaic
    refuses the one-row DMA into it)."""
    from nnstreamer_tpu.ops.pallas import decode_attention as da

    s, layers, hq, hkv, m, hd = 64, 2, 16, 2, 2048, 256
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn = jax.jit(functools.partial(da._decode_pallas, layer_axis=1,
                                   block=da.kv_block(m),
                                   grain=da.read_grain(m), interpret=False),
                 donate_argnums=(3, 4))
    row = sd((s, hkv, 1, hd))
    compiled = fn.lower(
        sd((s, hq, 1, hd)), row, row, sd((s, layers, hkv, m, hd)),
        sd((s, layers, hkv, m, hd)), sd((), jnp.int32), sd((s,), jnp.int32),
        sd((s,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * s * layers * hkv * m * hd * 4
    assert mem.temp_size_in_bytes < (8 << 20)
    assert "tpu_custom_call" in compiled.as_text()
