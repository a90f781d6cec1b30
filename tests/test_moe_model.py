"""MoE streaming transformer model family (models/moe_transformer.py).

Covers: zoo resolution + pipeline serving through tensor_filter,
expert-parallel sharded inference == single-device oracle, router metrics
via the moe_metrics collection, and composition with sequence windows
(aggregator → filter), mirroring how the stream_transformer family is
exercised."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.core.types import TensorsConfig, TensorsInfo
from nnstreamer_tpu.core import Caps
from nnstreamer_tpu.graph import Pipeline

SPEC = ("zoo://moe_transformer?layers=2&dim=32&heads=4&experts=4&seq=16"
        "&dtype=float32")


def test_zoo_resolution_and_shapes():
    from nnstreamer_tpu.models.zoo import get_model

    b = get_model(SPEC)
    assert b.in_info[0].shape == (1, 16, 32)
    assert b.out_info[0].shape == (1, 16, 32)
    x = np.random.default_rng(0).normal(size=(1, 16, 32)).astype(np.float32)
    out = jax.jit(b.fn())(x)
    assert out.shape == (1, 16, 32)
    assert np.isfinite(np.asarray(out)).all()


def test_pipeline_serving():
    p = Pipeline()
    frames = [np.random.default_rng(i).normal(size=(1, 16, 32))
              .astype(np.float32) for i in range(4)]
    src = p.add_new("appsrc", caps=Caps.tensors(TensorsConfig(
        TensorsInfo.from_strings("32:16:1", "float32"))), data=frames)
    filt = p.add_new("tensor_filter", framework="xla-tpu", model=SPEC)
    sink = p.add_new("tensor_sink", store=True)
    Pipeline.link(src, filt, sink)
    p.run(timeout=120)
    assert sink.num_buffers == 4
    assert sink.buffers[0].memories[0].shape == (1, 16, 32)


def test_expert_parallel_equals_single_device():
    from nnstreamer_tpu.models.moe_transformer import make_ep_infer
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    b = get_model(SPEC + "&batch=2")
    x = np.random.default_rng(1).normal(size=(2, 16, 32)).astype(np.float32)
    want = np.asarray(jax.jit(b.fn())(x))
    mesh = make_mesh({"data": 2, "expert": 4})
    jitted, placed = make_ep_infer(b, mesh)
    got = np.asarray(jitted(placed, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ep_param_shardings_rule():
    from nnstreamer_tpu.models.moe_transformer import ep_param_shardings
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh
    from jax.sharding import PartitionSpec as P

    b = get_model(SPEC)
    mesh = make_mesh({"data": 2, "expert": 4})
    sh = ep_param_shardings(b.params, mesh, 4)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    expert_leaves = [("/".join(str(getattr(k, "key", k)) for k in path), s)
                     for path, s in flat if s.spec == P("expert")]
    assert expert_leaves, "no expert-sharded leaves found"
    for name, _ in expert_leaves:
        assert "moe_block" in name, name


def test_router_metrics_collection():
    from nnstreamer_tpu.models.moe_transformer import MoEStreamTransformer

    model = MoEStreamTransformer(layers=2, dim=32, heads=4, n_experts=4,
                                 dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 16, 32)).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), x)
    out, aux = model.apply(variables, x, mutable=["moe_metrics"])
    metrics = aux["moe_metrics"]["moe_block_1"]
    lb = float(metrics["load_balance_loss"][0])
    counts = np.asarray(metrics["expert_counts"][0])
    assert lb >= 1.0 - 1e-3
    assert counts.sum() == 16  # every token routed


def test_ep_infer_rejects_indivisible_batch():
    from nnstreamer_tpu.models.moe_transformer import make_ep_infer
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    b = get_model(SPEC)  # batch=1 bundle
    mesh = make_mesh({"data": 2, "expert": 4})
    infer, placed = make_ep_infer(b, mesh)
    with pytest.raises(ValueError, match="divisible"):
        infer(placed, jnp.zeros((1, 16, 32), jnp.float32))
    # dp_axis=None serves any batch, replicated
    infer1, placed1 = make_ep_infer(b, mesh, dp_axis=None)
    out = infer1(placed1, jnp.zeros((1, 16, 32), jnp.float32))
    assert out.shape == (1, 16, 32)


@pytest.mark.parametrize("sp_mode", ["ring", "a2a"])
def test_sp_ep_composed_equals_single_device(sp_mode):
    """Sequence-parallel attention × expert-parallel MoE on one 2D mesh
    equals the single-device oracle (long-context + experts composed)."""
    from nnstreamer_tpu.models.moe_transformer import make_sp_ep_infer
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    b = get_model(SPEC)  # seq=16, experts=4, float32
    x = np.random.default_rng(2).normal(size=(1, 16, 32)).astype(np.float32)
    want = np.asarray(jax.jit(b.fn())(x))
    mesh = make_mesh({"sp": 2, "expert": 4})
    infer, placed = make_sp_ep_infer(b, mesh, sp_mode=sp_mode)
    got = np.asarray(infer(placed, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_sp_ep_rejects_indivisible_sequence():
    from nnstreamer_tpu.models.moe_transformer import make_sp_ep_infer
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    b = get_model(SPEC.replace("seq=16", "seq=15"))
    mesh = make_mesh({"sp": 2, "expert": 4})
    infer, placed = make_sp_ep_infer(b, mesh)
    with pytest.raises(ValueError, match="divisible"):
        infer(placed, jnp.zeros((1, 15, 32), np.float32))


def test_sp_ep_honors_nondefault_capacity_factor():
    """The rebuilt sp×ep model must reuse the bundle's capacity_factor —
    a default-capacity rebuild would drop different tokens than the
    oracle."""
    from nnstreamer_tpu.models.moe_transformer import make_sp_ep_infer
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    spec = SPEC + "&capacity_factor=0.5"
    b = get_model(spec)
    x = np.random.default_rng(5).normal(size=(1, 16, 32)).astype(np.float32)
    want = np.asarray(jax.jit(b.fn())(x))
    mesh = make_mesh({"sp": 2, "expert": 4})
    infer, placed = make_sp_ep_infer(b, mesh)
    got = np.asarray(infer(placed, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ep_bundle_serves_through_filter():
    """tensor_filter serves the expert-sharded MoE pjit program (pod-slice
    offload path), equal to the unsharded oracle."""
    from nnstreamer_tpu.core.buffer import TensorMemory
    from nnstreamer_tpu.filters.base import FilterProps
    from nnstreamer_tpu.filters.xla import XLAFilter
    from nnstreamer_tpu.models.moe_transformer import ep_bundle
    from nnstreamer_tpu.models.zoo import get_model
    from nnstreamer_tpu.parallel import make_mesh

    b = get_model(SPEC + "&batch=2")
    mesh = make_mesh({"data": 2, "expert": 4})
    served = ep_bundle(b, mesh)
    filt = XLAFilter()
    filt.open(FilterProps(model=served))
    x = np.random.default_rng(3).normal(size=(2, 16, 32)).astype(np.float32)
    got = filt.invoke([TensorMemory(x)])[0].host()
    ref = np.asarray(jax.jit(b.fn())(x))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
