"""Tensor-parallel serving tier (DESIGN.md §4.12).

The contract: an N-device engine is TOKEN-IDENTICAL to the 1-device
engine across the whole serving stack — dense, pruned (sliced shapes),
sub-byte packed, paged KV, speculative — because TP sharding is
column/head-parallel by construction: every output column and KV head
lives wholly on one device, no contraction is split across devices, no
cross-device reduction reassociates a sum. And the memory claim: a
device's share of params and KV arena shrinks ~1/tp (replication
fallbacks excepted).

The 4-device cases need fake host devices:

    REPRO_MULTI_DEVICE=1 \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m pytest tests/test_tp_engine.py

and skip themselves on 1-device hosts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import (kv_cache_specs, make_plan,
                                        serving_axes_for,
                                        serving_param_specs)
from repro.kernels import decode_attn as da
from repro.kernels import gemm_core, ops
from repro.launch.engine import build_engine, engine_serve
from repro.launch.mesh import make_tp_mesh

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs REPRO_MULTI_DEVICE=1 "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4")

ARCH = "internlm2-1.8b"


# ------------------------------------------------------------ kernel layer
@needs4
def test_tp_gemm_dense_exact():
    mesh = make_tp_mesh(4)
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (8, 96), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (96, 128), jnp.float32)
    want = gemm_core.gemm(x, w, backend="xla-ref")
    got = gemm_core.tp_gemm(x, w, mesh=mesh, backend="xla-ref")
    # column-parallel: each output column is computed by exactly one
    # device running the single-device kernel — bitwise equality
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs4
def test_tp_gemm_epilogues_exact():
    mesh = make_tp_mesh(4)
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (64, 128), jnp.float32)
    mask = (jax.random.uniform(jax.random.fold_in(k, 2), (128,)) > 0.5
            ).astype(jnp.float32)
    scale = jax.random.uniform(jax.random.fold_in(k, 3), (128,)) + 0.5
    for rhs_ops in [(gemm_core.col_mask(mask),),
                    (gemm_core.dequant(scale),),
                    (gemm_core.dequant(scale), gemm_core.col_mask(mask))]:
        want = gemm_core.gemm(x, w, rhs_ops, backend="xla-ref")
        got = gemm_core.tp_gemm(x, w, rhs_ops, mesh=mesh, backend="xla-ref")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs4
def test_tp_gemm_packed_exact():
    from repro.core.quant import pack_codes
    mesh = make_tp_mesh(4)
    k = jax.random.PRNGKey(2)
    K, N, bits = 64, 128, 4
    x = jax.random.normal(k, (4, K), jnp.float32)
    codes = jax.random.randint(jax.random.fold_in(k, 1), (K, N), -8, 8,
                               jnp.int32)
    scale = jax.random.uniform(jax.random.fold_in(k, 2), (N,)) + 0.5
    packed = pack_codes(codes, bits)
    want = gemm_core.gemm(x, packed,
                          (gemm_core.unpack_dequant(bits, scale),),
                          backend="xla-ref")
    got = gemm_core.tp_gemm(x, packed,
                            (gemm_core.unpack_dequant(bits, scale),),
                            mesh=mesh, backend="xla-ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs4
def test_tp_gemm_rejects_indivisible_n():
    mesh = make_tp_mesh(4)
    x = jnp.zeros((4, 32), jnp.float32)
    w = jnp.zeros((32, 66), jnp.float32)    # 66 % 4 != 0
    with pytest.raises(ValueError):
        gemm_core.tp_gemm(x, w, mesh=mesh, backend="xla-ref")


@needs4
def test_tp_decode_attn_exact():
    mesh = make_tp_mesh(4)
    k = jax.random.PRNGKey(3)
    B, S, KVh, dh, g = 2, 32, 4, 16, 2
    q = jax.random.normal(k, (B, KVh, g, dh), jnp.float32)
    kc = jnp.zeros((B, S, KVh, dh), jnp.float32)
    vc = jnp.zeros((B, S, KVh, dh), jnp.float32)
    kc = kc.at[:, :20].set(
        jax.random.normal(jax.random.fold_in(k, 1), (B, 20, KVh, dh)))
    vc = vc.at[:, :20].set(
        jax.random.normal(jax.random.fold_in(k, 2), (B, 20, KVh, dh)))
    pos = jnp.asarray([19, 11], jnp.int32)
    want = ops.decode_attn_op(q, kc, vc, pos, backend="xla-ref")
    got = da.tp_decode_attn(q, kc, vc, pos, mesh=mesh, backend="xla-ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs4
def test_tp_decode_attn_stacked_layer_exact():
    """The stacked (nb, B, S, KVh*dh) arena read at a layer index splits
    its merged axis at whole heads: each device's kernel reads its heads
    of that layer in place, identical to the unsharded op."""
    mesh = make_tp_mesh(4)
    k = jax.random.PRNGKey(4)
    nb, B, S, KVh, dh, g = 3, 2, 32, 4, 16, 2
    q = jax.random.normal(k, (B, KVh, g, dh), jnp.float32)
    ka = jax.random.normal(jax.random.fold_in(k, 1), (nb, B, S, KVh * dh))
    va = jax.random.normal(jax.random.fold_in(k, 2), (nb, B, S, KVh * dh))
    pos = jnp.asarray([19, 11], jnp.int32)
    for layer in range(nb):
        want = ops.decode_attn_op(q, ka, va, pos, layer=layer,
                                  backend="xla-ref")
        got = da.tp_decode_attn(q, ka, va, pos, layer=jnp.int32(layer),
                                mesh=mesh, backend="xla-ref")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs4
def test_tp_decode_attn_rejects_indivisible_heads():
    mesh = make_tp_mesh(4)
    q = jnp.zeros((1, 3, 2, 8), jnp.float32)      # 3 KV heads % 4 != 0
    kc = jnp.zeros((1, 16, 3, 8), jnp.float32)
    with pytest.raises(ValueError):
        da.tp_decode_attn(q, kc, kc, jnp.zeros((1,), jnp.int32),
                          mesh=mesh)


# ----------------------------------------------------------- spec mapping
@needs4
def test_serving_param_specs_maps_derived_keys():
    mesh = make_tp_mesh(4)
    plan = make_plan(mesh, mode="tp")
    axes = {"blocks.0.mlp.w1": ("embed", "mlp")}
    params = {"blocks.0.mlp.w1.codes": np.zeros((128, 256), np.int8),
              "blocks.0.mlp.w1.packed4": np.zeros((16, 256), np.int32),
              "blocks.0.mlp.w1.scale": np.zeros((2,), np.float32),
              "unrelated": np.zeros((7,), np.float32)}
    specs = serving_param_specs(plan, axes, params)
    # codes and packed words shard like the base weight (N on "model");
    # scales and unmapped leaves replicate
    assert specs["blocks.0.mlp.w1.codes"][1] == "model"
    assert specs["blocks.0.mlp.w1.packed4"][1] == "model"
    assert tuple(specs["blocks.0.mlp.w1.scale"]) in ((), (None,))
    assert tuple(specs["unrelated"]) in ((), (None,))


def test_serving_axes_for_suffixes():
    axes = {"w": ("embed", "mlp")}
    assert serving_axes_for("w", axes) == ("embed", "mlp")
    assert serving_axes_for("w.codes", axes) == ("embed", "mlp")
    assert serving_axes_for("w.packed4", axes) == ("embed", "mlp")
    assert serving_axes_for("w.scale", axes) == ("layers",)
    assert serving_axes_for("w.other", axes) is None
    assert serving_axes_for("missing.codes", axes) is None


def test_serving_param_specs_never_split_a_contraction():
    """Row-parallel projections (o-proj, down-proj) shard their output
    columns: split along K, GSPMD would sum partial dots across devices
    and tp>1 logits would drift from tp=1. Shapes only, so an abstract
    mesh stands in for four devices."""
    mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
    plan = make_plan(mesh, mode="tp")
    axes = {"wq": ("layers", "embed", "q_heads"),
            "wo": ("layers", "q_heads", "embed"),
            "w_down": ("layers", "mlp", "embed"),
            "w_odd": ("layers", "mlp", "embed")}
    params = {"wq": np.zeros((2, 128, 64), np.float32),
              "wo": np.zeros((2, 64, 128), np.float32),
              "w_down.packed4": np.zeros((2, 32, 128), np.int32),
              "w_odd": np.zeros((2, 64, 126), np.float32)}
    specs = serving_param_specs(plan, axes, params)
    assert tuple(specs["wq"]) == (None, None, "model")
    assert tuple(specs["wo"]) == (None, None, "model")
    assert tuple(specs["w_down.packed4"]) == (None, None, "model")
    # an output width the mesh cannot divide replicates
    assert tuple(specs["w_odd"]) == (None, None, None)


@needs4
def test_kv_cache_specs_head_axis():
    mesh = make_tp_mesh(4)
    shapes = {"blocks.0.k": (2, 4, 64, 4, 16),       # KVh=4: shard
              "blocks.0.v": (2, 4, 64, 4, 16),
              "blocks.1.k": (2, 4, 64, 3, 16),       # KVh=3: replicate
              "blocks.0.k_scale": (2, 8, 16, 4),     # paged scale: shard
              "blocks.0.h": (2, 4, 32, 7)}           # recurrent state
    specs = kv_cache_specs(mesh, shapes)
    assert specs["blocks.0.k"][3] == "model"
    assert specs["blocks.0.v"][3] == "model"
    assert tuple(specs["blocks.1.k"]) in ((), (None,) * 5)
    assert specs["blocks.0.k_scale"][3] == "model"
    assert tuple(specs["blocks.0.h"]) in ((), (None,) * 4)


def test_kv_cache_specs_merged_axis_whole_heads():
    """A contiguous arena stores K/V as (nb, B, S, KVh*dh): the merged
    axis shards only when the leaf's KV heads divide the mesh, so a
    split never lands inside a head; otherwise (or without head counts)
    it replicates, however the merged width divides."""
    mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
    shapes = {"blocks.0.k": (2, 4, 64, 4 * 16),     # KVh=4: shard
              "blocks.0.v": (2, 4, 64, 4 * 16),
              "blocks.1.k": (2, 4, 64, 6 * 16),     # KVh=6: replicate
              "blocks.1.v": (2, 4, 64, 6 * 16),
              "blocks.0.h": (2, 4, 32, 7)}           # recurrent state
    heads = {"blocks.0.k": 4, "blocks.0.v": 4, "blocks.1.k": 6,
             "blocks.1.v": 6}
    specs = kv_cache_specs(mesh, shapes, heads)
    assert tuple(specs["blocks.0.k"]) == (None, None, None, "model")
    assert tuple(specs["blocks.0.v"]) == (None, None, None, "model")
    assert tuple(specs["blocks.1.k"]) in ((), (None,) * 4)
    assert tuple(specs["blocks.0.h"]) in ((), (None,) * 4)
    assert all(tuple(sp) in ((), (None,) * 4)
               for sp in kv_cache_specs(mesh, shapes).values())


# ------------------------------------------------------------ engine layer
@needs4
@pytest.mark.parametrize("kw", [
    pytest.param({}, id="dense"),
    pytest.param(dict(pruned=True, sparsity=0.5), id="pruned_s50"),
    pytest.param(dict(packed=True, bits_init=4.0), id="packed_b4"),
    pytest.param(dict(paged=True, page_size=8), id="paged"),
])
def test_tp4_engine_token_identity(kw):
    base = engine_serve(ARCH, True, [12, 5], 8, verbose=False, **kw)
    tp = engine_serve(ARCH, True, [12, 5], 8, verbose=False, tp=4, **kw)
    assert sorted(base) == sorted(tp)
    for rid in base:
        np.testing.assert_array_equal(base[rid], tp[rid])


@needs4
def test_tp4_speculative_token_identity():
    base = engine_serve(ARCH, True, [12, 5], 8, verbose=False,
                        speculative=True, draft_k=4)
    tp = engine_serve(ARCH, True, [12, 5], 8, verbose=False,
                      speculative=True, draft_k=4, tp=4)
    for rid in base:
        np.testing.assert_array_equal(base[rid], tp[rid])


@needs4
def test_tp4_chunked_prefill_token_identity():
    base = engine_serve(ARCH, True, [12, 5, 21], 8, verbose=False)
    st = {}
    tp = engine_serve(ARCH, True, [12, 5, 21], 8, verbose=False, tp=4,
                      prefill_chunk=8, stats=st)
    for rid in base:
        np.testing.assert_array_equal(base[rid], tp[rid])
    assert st["decode_steps_mid_prefill"] > 0


@needs4
def test_tp2_per_device_bytes_shrink():
    # the smoke arch has 2 KV heads / 4 q heads / 256 mlp / 512 vocab:
    # every projection and the whole arena divide tp=2, so KV halves
    # exactly and params land within a few replicated norm vectors of 1/2
    eng, _ = build_engine(ARCH, True, tp=2)
    full = eng.param_bytes()
    per = eng.param_bytes(per_device=True)
    assert full / 2 <= per <= 0.55 * full, (per, full)
    assert eng.kv_bytes(per_device=True) * 2 == eng.kv_bytes()
    assert eng.serving_meta["tp"]["replicated_fallbacks"] == []


@needs4
def test_tp4_kv_replicates_when_heads_indivisible():
    # 2 KV heads % 4 != 0: the arena must replicate (per-device KV share
    # = full) while q-head/mlp/vocab params still shard — and decode must
    # stay token-identical regardless (covered by the matrix above)
    eng, _ = build_engine(ARCH, True, tp=4)
    assert eng.kv_bytes(per_device=True) == eng.kv_bytes()
    assert eng.param_bytes(per_device=True) < eng.param_bytes()


@needs4
def test_tp2_paged_per_device_kv_shrink():
    eng, _ = build_engine(ARCH, True, tp=2, paged=True, page_size=8)
    # pools are empty of live pages at build; compare the pinned pool
    full = sum(eng._leaf_nbytes(lf, False)
               for lf in jax.tree_util.tree_leaves(eng.caches))
    per = sum(eng._leaf_nbytes(lf, True)
              for lf in jax.tree_util.tree_leaves(eng.caches))
    assert per * 2 == full


@needs4
def test_make_tp_mesh_shape():
    mesh = make_tp_mesh(4)
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    with pytest.raises(ValueError):
        make_tp_mesh(jax.device_count() + 1)
