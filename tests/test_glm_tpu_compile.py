"""The two kernels of the latent-attention / expert family compiled for a
described v5e chip at GLM-4.7-Flash's widths, by the TPU compiler that is
installed beside JAX: Mosaic's own passes run (they refused a one-row DMA
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
