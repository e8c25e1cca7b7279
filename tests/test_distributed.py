"""Sharding rules, gradient compression, BOPs accounting, saliency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core.bops import LayerMacs, model_bops
from repro.core.qadg import build_qadg
from repro.core.saliency import SaliencyConfig, global_redundancy_partition
from repro.distributed.collectives import (_dequantize_blockwise,
                                           _quantize_blockwise)
from repro.distributed.sharding import batch_spec, make_plan
from repro.launch.mesh import make_mesh
from repro.models.cnn import CNN, VGG7


def _mesh():
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def test_sharding_plan_divisibility_fallback():
    mesh = _mesh()
    plan = make_plan(mesh)
    # model axis is size 1 here: every spec must be valid (no exceptions)
    spec = plan.spec_for("w", ("embed", "mlp"), (64, 128))
    assert isinstance(spec, P)


def test_sharding_plan_records_fallbacks():
    # fake a mesh-like object with a model axis of 16 via abstract mesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    plan = make_plan(mesh)
    spec = plan.spec_for("w", ("embed", "kv_heads"), (64, 24))
    # 24 % 16 != 0 -> fallback recorded, axis replicated
    assert spec == P(None, None)
    assert any(a == "kv_heads" for _, a, _ in plan.fallbacks)


def test_fsdp_rules():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    plan = make_plan(mesh, fsdp=True)
    spec = plan.spec_for("w", ("embed", "mlp"), (8192, 32768))
    assert spec == P(("pod", "data"), "model")


def test_arch_overrides_respected():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    plan = make_plan(mesh, overrides={"fsdp": True, "experts_axis": None,
                                      "expert_mlp_axis": "model",
                                      "base_optimizer": "momentum"})
    spec = plan.spec_for("we", ("experts", "embed", "expert_mlp"),
                         (8, 6144, 32768))
    assert spec == P(None, "data", "model")


def test_batch_spec_sp():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert batch_spec(mesh) == P(("pod", "data"))
    assert batch_spec(mesh, shard_seq=True) == P(None, ("pod", "data"))


# ------------------------------------------------------ grad compression
def test_blockwise_quantization_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 3
    codes, scale = _quantize_blockwise(x)
    xr = _dequantize_blockwise(codes, scale)[: x.size]
    # int8 with per-block max scaling: error <= scale/2 per element
    err = np.abs(np.asarray(x) - xr)
    bound = np.repeat(np.asarray(scale)[:, 0], 256)[: x.size] * 0.5 + 1e-7
    assert np.all(err <= bound)


def test_compressed_psum_semantics():
    """compressed all-reduce ~= psum within int8 quantization error."""
    from repro.distributed.collectives import compressed_psum
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 512))

    def f(xs):
        return compressed_psum(xs[0], "data")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                        check_vma=False)(x)
    expect = jnp.sum(x, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_error_feedback_accumulates():
    from repro.distributed.collectives import compressed_grad_allreduce
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (300,)) * 1e-3}
    mean, ef = compressed_grad_allreduce(g, mesh, axis_names=("data",))
    # sent + residual == original (error feedback identity)
    sent = g["w"] - ef["w"]
    np.testing.assert_allclose(np.asarray(sent + ef["w"]),
                               np.asarray(g["w"]), rtol=1e-6)


# -------------------------------------------------------------- BOPs
def test_bops_reduction_from_pruning_and_quant():
    m = CNN(VGG7)
    params = m.init(jax.random.PRNGKey(0))
    qadg = build_qadg(m.build_graph().graph)
    qparams = m.init_qparams(params, bits_init=32.0)
    macs = m.layer_macs(batch=1)

    full = model_bops(qadg, params, qparams, macs)
    assert full["rel_bops"] == pytest.approx(1.0, rel=1e-6)

    q8 = m.init_qparams(params, bits_init=8.0)
    quantized = model_bops(qadg, params, q8, macs)
    assert quantized["rel_bops"] == pytest.approx(0.25, rel=1e-2)

    masks = qadg.space.init_masks()
    masks = {k: v.at[: len(v) // 2].set(0.0) for k, v in masks.items()}
    pruned = model_bops(qadg, params, q8, macs, masks=masks)
    assert pruned["rel_bops"] < quantized["rel_bops"] * 0.6


# ---------------------------------------------------------- saliency
def test_partition_sizes_exact():
    m = CNN(VGG7)
    params = m.init(jax.random.PRNGKey(0))
    qadg = build_qadg(m.build_graph().graph)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1), p.shape), params)
    for n_red in (0, 7, 100):
        part = global_redundancy_partition(qadg.space, params, grads,
                                           jnp.int32(n_red))
        total = sum(int(jnp.sum(v)) for v in part.values())
        assert total == n_red


def test_partition_pinned_sticky():
    m = CNN(VGG7)
    params = m.init(jax.random.PRNGKey(0))
    qadg = build_qadg(m.build_graph().graph)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    p1 = global_redundancy_partition(qadg.space, params, grads,
                                     jnp.int32(10))
    p2 = global_redundancy_partition(qadg.space, params, grads,
                                     jnp.int32(20), pinned=p1)
    for k in p1:
        # every previously-redundant unit remains redundant
        assert np.all(np.asarray(p2[k]) >= np.asarray(p1[k]))
    assert sum(int(jnp.sum(v)) for v in p2.values()) == 20
