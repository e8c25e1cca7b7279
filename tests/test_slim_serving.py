"""Slim serving: the engine runs on physically pruned LM shapes.

The contract under test (the transformer analogue of
`test_qadg.test_cnn_masks_preserve_forward_of_kept_units`): a masked unit
contributes *exact zeros* to every downstream tensor, so slicing it away
(`PruningSpace.materialize` -> `derive_slim_plan` -> `LM.apply_slim_plan`)
must not change a single logit on the kept units — dense fake-quant AND
compressed int-code decode, forward AND cached decode, all the way up to
the continuous-batching engine, whose pruned decode must be
token-identical to the masked dense reference while its KV arena and
served params shrink with realized sparsity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.groups import GroupFamily, Member, PruningSpace
from repro.core.qadg import build_qadg
from repro.core.subnet import (compress_lm, compression_report,
                               default_min_keep, derive_slim_plan,
                               magnitude_keep_masks, prepare_serving,
                               prune_lm, tree_bytes)
from repro.launch.engine import (build_engine, build_masked_reference_engine,
                                 synthetic_prompts)
from repro.models.transformer import LM

ARCH = "internlm2-1.8b"
SPARSITY = 0.5


def _f32_lm(arch=ARCH):
    cfg = get_arch(arch, smoke=True)
    if cfg.dtype != "float32":       # tight parity needs f32 weights
        cfg = dataclasses.replace(cfg, dtype="float32")
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    return lm, params


def _masks(lm, params, sparsity=SPARSITY):
    qadg = build_qadg(lm.build_graph().graph)
    return qadg, magnitude_keep_masks(qadg.space, params, sparsity,
                                      min_keep=default_min_keep(lm.cfg))


# -------------------------------------------------- masked vs sliced parity
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["dense", "compressed"])
def test_lm_masked_vs_sliced_logit_parity(compressed):
    """Masked LM and physically sliced LM produce identical logits on the
    kept units — attention-head and MLP-hidden families pruned, the
    residual family untouched (it is pinned non-prunable by embed/head)."""
    lm, params = _f32_lm()
    qparams = lm.init_qparams(params)
    qadg, masks = _masks(lm, params)

    kinds = {f.kind for f in qadg.space.prunable_families()}
    assert kinds == {"head_group", "channel"}   # attn heads + mlp hidden
    # every prunable family actually lost units at this sparsity
    assert all(int(jnp.sum(masks[f.name])) < f.units
               for f in qadg.space.prunable_families())
    # the residual family exists and is non-prunable (so logits keep shape)
    resid = [f for f in qadg.space.families
             if not f.prunable and any(m.param == "embed" for m in f.members)]
    assert resid, "residual space lost its embed producer"

    masked = qadg.space.apply_masks(params, masks)

    slim = LM(lm.cfg)
    p_slim, q_slim, meta = prepare_serving(
        slim, dict(params), quantized=True, compressed=compressed,
        keep_masks=masks)
    assert meta["sparsity"] == pytest.approx(SPARSITY, abs=0.05)
    assert meta["param_bytes"] < tree_bytes(params)

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0, lm.cfg.vocab)
    lg_masked = lm.forward(masked, qparams, toks)
    lg_slim = slim.forward(p_slim, q_slim, toks)
    assert lg_slim.shape == lg_masked.shape    # head/residual not pruned
    np.testing.assert_allclose(np.asarray(lg_slim), np.asarray(lg_masked),
                               rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.argmax(np.asarray(lg_slim), -1),
                          np.argmax(np.asarray(lg_masked), -1))


@pytest.mark.parametrize("compressed", [False, True],
                         ids=["dense", "compressed"])
def test_lm_masked_vs_sliced_decode_parity(compressed):
    """Cached decode through the sliced KV arena matches the masked dense
    reference step for step (greedy tokens identical)."""
    lm, params = _f32_lm()
    qparams = lm.init_qparams(params)
    qadg, masks = _masks(lm, params)
    masked = qadg.space.apply_masks(params, masks)

    slim = LM(lm.cfg)
    p_slim, q_slim, _ = prepare_serving(
        slim, dict(params), quantized=True, compressed=compressed,
        keep_masks=masks)

    def greedy(model, p, q, steps=6):
        caches = model.init_cache(2, 16, dtype=jnp.float32)
        tok = jnp.zeros((2, 1), jnp.int32)
        step = jax.jit(model.decode_step)
        out = []
        for i in range(steps):
            lg, caches = step(p, q, caches, tok, jnp.int32(i))
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            out.append(np.asarray(tok[:, 0]))
        return np.stack(out)

    np.testing.assert_array_equal(greedy(slim, p_slim, q_slim),
                                  greedy(lm, masked, qparams))


def test_slim_plan_shapes_and_kv_arena():
    """The derived SlimPlan reports the surviving widths, the model
    reshapes at them, and init_cache allocates KV rows for surviving
    kv heads only (proportional byte shrink)."""
    lm, params = _f32_lm()
    cfg = lm.cfg
    slim = LM(cfg)
    sliced, plan = prune_lm(slim, dict(params), sparsity=SPARSITY)
    shp = plan.layer_shapes[0]
    assert shp.n_kv_heads < cfg.n_kv_heads
    assert shp.n_heads == shp.n_kv_heads * cfg.gqa_group
    assert shp.d_ff < cfg.d_ff
    kept = plan.kept_units[f"blocks.0.attn.kv_groups"]
    assert len(kept) == shp.n_kv_heads
    # sliced params carry the plan's widths
    assert sliced["blocks.0.attn.wk"].shape[-1] == shp.n_kv_heads * cfg.d_head
    assert sliced["blocks.0.mlp.w_gate"].shape[-1] == shp.d_ff

    full = LM(cfg).init_cache(2, 16, dtype=jnp.float32)
    slimc = slim.init_cache(2, 16, dtype=jnp.float32)
    assert tree_bytes(slimc) == \
        tree_bytes(full) * shp.n_kv_heads // cfg.n_kv_heads


def test_prune_then_compress_stacks():
    """Pruning composes with int-code compression: codes are emitted at
    the *sliced* shapes and the dequant-epilogue decode runs on them."""
    lm, params = _f32_lm()
    qparams = lm.init_qparams(params)
    slim = LM(lm.cfg)
    sliced, plan = prune_lm(slim, dict(params), sparsity=SPARSITY)
    subnet = compress_lm(slim, sliced, qparams)
    assert subnet.int_weights
    for name, codes in subnet.int_weights.items():
        assert codes.shape == sliced[name].shape, name


@pytest.mark.parametrize("arch", ["rwkv6-3b", "jamba-1.5-large-398b",
                                  "jamba2-3b"])
def test_pruned_decode_stateful_families(arch):
    """SSM/RWKV/hybrid(+MoE) subnets decode at sliced state widths: the
    recurrent caches (mamba h/conv, rwkv wkv) shrink with the plan and
    the decode stays finite. (MoE masked-vs-sliced parity is out of
    contract: a zeroed router column still wins softmax mass — see
    DESIGN.md §4.7.)"""
    lm, params = _f32_lm(arch)
    slim = LM(lm.cfg)
    p_slim, q_slim, meta = prepare_serving(
        slim, dict(params), quantized=False, prune_sparsity=0.4)
    assert meta["sparsity"] > 0.2
    assert tree_bytes(slim.init_cache(1, 16, dtype=jnp.float32)) < \
        tree_bytes(LM(lm.cfg).init_cache(1, 16, dtype=jnp.float32))
    caches = slim.init_cache(1, 16, dtype=jnp.float32)
    tok = jnp.zeros((1, 1), jnp.int32)
    step = jax.jit(slim.decode_step)
    for i in range(3):
        lg, caches = step(p_slim, q_slim, caches, tok, jnp.int32(i))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        assert np.all(np.isfinite(np.asarray(lg)))


def test_jamba_pruned_packed_artifact_serves():
    """A GETA deployment artifact of Jamba (Mamba inner channels and MLP
    units pruned, projections 4-bit packed) serves through the engine:
    the conv bias is sliced with its channels, the dt / B / C norms keep
    their widths, and every request comes back at its length."""
    lm, params = _f32_lm("jamba2-3b")
    key = jax.random.PRNGKey(3)
    params = {k: (jax.random.normal(key, v.shape) * 0.1
                  if k.endswith("conv_b") else v) for k, v in params.items()}
    slim = LM(lm.cfg)
    p, q, meta = prepare_serving(slim, dict(params), bits_init=4.0,
                                 packed=True, prune_sparsity=0.4)
    assert meta["sparsity"] > 0.2 and meta["packed_sites"]
    pre = "blocks.0.mamba."
    Di = slim.shapes[0].mamba_inner
    assert Di < lm.shapes[0].mamba_inner
    assert p[pre + "conv_b"].shape == (slim.n_blocks, Di)
    assert p[pre + "conv_w"].shape[-1] == Di
    assert p[pre + "dt_norm"].shape == params[pre + "dt_norm"].shape
    assert p[pre + "b_norm"].shape == params[pre + "b_norm"].shape
    assert any(k.startswith(pre + "in_proj_x.packed") for k in p)
    from repro.launch.engine import Engine
    eng = Engine(slim, p, q, max_slots=2, max_seq=24)
    for prompt in synthetic_prompts(lm.cfg, [7, 11, 5]):
        eng.submit(prompt, 6)
    out = eng.run()
    assert sorted(out) == [0, 1, 2]
    assert all(len(t) == 6 for t in out.values())


# ------------------------------------------------------- engine end to end
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["dense", "compressed"])
def test_engine_pruned_matches_masked_reference(compressed):
    """Acceptance: engine decode from a sparsity-0.5-pruned transformer is
    token-identical to the masked dense reference, with the KV arena and
    served param bytes reduced proportionally to realized sparsity."""
    lens, gen, slots = [6, 4, 5], 7, 2
    max_seq = max(lens) + gen
    eng, lm = build_engine(ARCH, True, compressed=compressed, pruned=True,
                           sparsity=SPARSITY, max_slots=slots,
                           max_seq=max_seq)
    ref, _ = build_masked_reference_engine(ARCH, True, sparsity=SPARSITY,
                                           max_slots=slots, max_seq=max_seq)
    for p in synthetic_prompts(lm.cfg, lens):
        eng.submit(p, gen)
        ref.submit(p, gen)
    out, want = eng.run(), ref.run()
    assert sorted(out) == sorted(want)
    for rid in want:
        np.testing.assert_array_equal(out[rid], want[rid],
                                      err_msg=f"request {rid}")
    # realized-shape wins: KV rows for surviving kv heads only, and the
    # prunable block weights shrink proportionally to sparsity (embed/head
    # are non-prunable and dominate the smoke model's total)
    sp = eng.serving_meta["sparsity"]
    blk = lambda e: tree_bytes({k: v for k, v in e.params.items()
                                if k.startswith("blocks.")})
    assert eng.kv_bytes() == ref.kv_bytes() // 2      # 1 of 2 kv groups
    assert blk(eng) <= blk(ref) * (1.0 - sp) + 2**12
    assert eng.param_bytes() < ref.param_bytes()
    assert eng.serving_meta["kv_bytes"] == eng.kv_bytes()


def test_engine_pruned_slot_reuse_and_mixed_lengths():
    """Continuous batching invariants survive the slim shapes: per-slot
    positions, admission into freed slots, mixed budgets."""
    eng, lm = build_engine(ARCH, True, pruned=True, sparsity=SPARSITY,
                           max_slots=1, max_seq=16)
    alone, _ = build_engine(ARCH, True, pruned=True, sparsity=SPARSITY,
                            max_slots=1, max_seq=16)
    prompts = synthetic_prompts(lm.cfg, [5, 3, 5])
    want = alone.submit(prompts[2], 6)
    want = alone.run()[want]
    for p, g in zip(prompts, (4, 6, 6)):
        eng.submit(p, g)
    out = eng.run()
    np.testing.assert_array_equal(out[2], want)


# ------------------------------------------------------------- satellites
def test_materialize_rejects_out_of_range_layout():
    """A mis-specified layout must raise (naming family and member), not
    silently truncate to a wrong slice."""
    w = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    # family claims 4 units x unit_size 2 = 8 elements on an axis of 6
    fam = GroupFamily("bad.family", 4, [Member("w", 0, unit_size=2)])
    space = PruningSpace([fam])
    mask = jnp.ones((4,)).at[0].set(0.0)
    with pytest.raises(ValueError, match="bad.family.*w"):
        space.materialize({"w": w}, {"bad.family": mask})


def test_compress_lm_records_skipped_sites():
    """Non-routed weights (MoE einsum tensors) stay dense; their names
    must land in Subnet.meta['skipped_sites'] and show in the report."""
    lm, params = _f32_lm("grok-1-314b")
    qparams = lm.init_qparams(params)
    subnet = compress_lm(lm, params, qparams)
    skipped = subnet.meta["skipped_sites"]
    assert skipped and all(".moe." in n for n in skipped)
    assert not any(n in subnet.int_weights for n in skipped)
    report = compression_report("grok-1-314b", subnet.meta)
    assert f"{len(skipped)} non-routed sites kept dense" in report


def test_derive_slim_plan_validates_kept_units():
    """A kept_units dict inconsistent with the sliced shapes is a hard
    error, not a silently wrong plan."""
    lm, params = _f32_lm()
    slim = LM(lm.cfg)
    sliced, plan = prune_lm(slim, dict(params), sparsity=SPARSITY)
    bad = dict(plan.kept_units)
    fam = "blocks.0.attn.kv_groups"
    bad[fam] = bad[fam][:-1] if len(bad[fam]) > 1 else np.array([0, 1])
    with pytest.raises(ValueError, match="kv_groups"):
        derive_slim_plan(slim, sliced, bad)


def test_moe_floor_keeps_top_k_experts():
    """Magnitude masks never prune the expert family below the router's
    top_k (a top-k over fewer experts than k cannot execute)."""
    lm, params = _f32_lm("grok-1-314b")
    qadg = build_qadg(lm.build_graph().graph)
    masks = magnitude_keep_masks(qadg.space, params, 0.95,
                                 min_keep=default_min_keep(lm.cfg))
    for fam in qadg.space.prunable_families():
        if fam.kind == "expert":
            assert int(jnp.sum(masks[fam.name])) >= lm.cfg.moe.top_k
