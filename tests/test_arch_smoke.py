"""Per-architecture smoke tests (required deliverable f):

Every assigned arch instantiates its REDUCED config and runs one forward +
one GETA train step + one decode step on CPU, asserting output shapes and
finiteness. The FULL configs are exercised only by the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_arch, get_overrides
from repro.configs.base import CompressionConfig
from repro.core.qadg import build_qadg
from repro.data.synthetic import batch_for
from repro.launch.train import build_geta, make_geta_train_step
from repro.models.transformer import LM

COMP = CompressionConfig(
    target_sparsity=0.4, bit_lower=4, bit_upper=16, act_quant=False,
    warmup_steps=2, projection_periods=1, projection_steps=2,
    bit_reduction=2, pruning_periods=2, pruning_steps=2, cooldown_steps=2)


@pytest.mark.slow   # ~10s/arch: jits one full GETA train step per config
@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch, smoke=True)
    lm = LM(cfg)
    params, axes = lm.init(jax.random.PRNGKey(0))
    # every param has a logical-axes entry of matching rank
    for name, arr in params.items():
        assert name in axes, name
        assert len(axes[name]) == arr.ndim, (name, axes[name], arr.shape)
    qparams = lm.init_qparams(params, bits_init=16.0)
    batch = batch_for(cfg, seed=0, step=0, batch=2, seq=16)

    logits = lm.forward(params, qparams, batch["tokens"],
                        batch.get("vision_embeds"))
    S_total = 16 if cfg.family != "vlm" else 16 + cfg.vision_patches - \
        cfg.vision_patches + 16  # text + patches handled inside
    assert logits.shape[0] == 2
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    base_opt = get_overrides(arch).get("base_optimizer", "adamw")
    qadg, qasso = build_geta(lm, COMP, lr=1e-3, base_optimizer=base_opt)
    qadg.space.validate(params)
    qstate = qasso.init(params, qparams)
    step = jax.jit(make_geta_train_step(lm, qasso))
    p2, q2, s2, metrics = step(params, qparams, qstate, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(s2.step) == 1


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_arch(arch, smoke=True)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    caches = lm.init_cache(2, 32, dtype=jnp.float32)
    tok_shape = (2, 1, cfg.num_codebooks) if cfg.num_codebooks else (2, 1)
    tok = jnp.zeros(tok_shape, jnp.int32)
    logits, caches2 = jax.jit(lm.decode_step)(params, None, caches, tok,
                                              jnp.int32(0))
    assert logits.shape[0] == 2
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    # caches updated in place-shape
    for k in caches:
        assert caches2[k].shape == caches[k].shape


def test_decode_matches_forward_dense():
    """Token-by-token decode reproduces the teacher-forced forward logits
    (dense arch, no quant)."""
    cfg = get_arch("internlm2-1.8b", smoke=True)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    full = lm.forward(params, None, toks)
    caches = lm.init_cache(1, 16, dtype=jnp.float32)
    outs = []
    for p in range(8):
        lg, caches = lm.decode_step(params, None, caches, toks[:, p:p+1],
                                    jnp.int32(p))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=2e-3,
                               atol=2e-3)


def test_decode_matches_forward_rwkv():
    cfg = get_arch("rwkv6-3b", smoke=True)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab)
    full = lm.forward(params, None, toks)
    caches = lm.init_cache(1, 16, dtype=jnp.float32)
    outs = []
    for p in range(6):
        lg, caches = lm.decode_step(params, None, caches, toks[:, p:p+1],
                                    jnp.int32(p))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=2e-3,
                               atol=2e-3)


def test_decode_matches_forward_hybrid():
    import dataclasses
    cfg = get_arch("jamba-1.5-large-398b", smoke=True)
    # parity check needs drop-free routing: the teacher-forced forward
    # routes all tokens jointly (capacity can bind), decode routes one
    # token at a time (capacity never binds) — raise the capacity factor
    # so both paths keep every token.
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab)
    full = lm.forward(params, None, toks)
    caches = lm.init_cache(1, 16, dtype=jnp.float32)
    outs = []
    for p in range(6):
        lg, caches = lm.decode_step(params, None, caches, toks[:, p:p+1],
                                    jnp.int32(p))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=5e-3,
                               atol=5e-3)


def test_blockwise_attention_matches_dense():
    from repro.models.layers import attention_blockwise, attention_dense
    B, S, H, KV, dh = 2, 256, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, dh))
    yd = attention_dense(q, k, v)
    yb = attention_blockwise(q, k, v, block=64)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(yd), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("arch,period,attn_at", [
    ("jamba-1.5-large-398b", 8, 4),      # attn_layer_period 8, offset 4
    ("jamba2-3b", 14, 7),                # attn_layer_period 14, offset 7
])
def test_hybrid_attention_at_the_published_offset(arch, period, attn_at):
    """A Jamba period attends at its published offset, with no positional
    encoding, and the parameter count follows the same layout: within
    0.1% of the program's own parameters (it leaves out the norms)."""
    from repro.models.transformer import layer_plan
    cfg = get_arch(arch)
    plan, n_blocks = layer_plan(cfg)
    assert len(plan) == period and n_blocks == cfg.n_layers // period
    assert [s.j for s in plan if s.mixer == "attn"] == [attn_at]
    assert not cfg.rope
    shapes = jax.eval_shape(lambda: LM(cfg).init(jax.random.PRNGKey(0))[0])
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert abs(cfg.param_count() - n) < 1e-3 * n
