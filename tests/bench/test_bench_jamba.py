"""Jamba (`bench/arch/jamba.py`, `bench/reference/jamba.py`) in the
benchmark. Through the program's serving path against the plain
reference, at the program's smoke size on seeded random weights: one-shot
prefill, then decode through the engine's contiguous arena, compared
logit for logit. The architecture module's weights are the program's
parameters, its slot the program's arena, and its counts are pinned by
hand at AI21-Jamba2-3B's widths. A tiny Jamba cell, added to a tiny
checkout with new files and entries only, runs correct, and the faults
and the fp8 control fail it."""
import dataclasses
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness, traffic
from conftest import DATA, REPO, add_cell, make_tiny_root
from test_bench_faults import _plant

ARCH = harness.load_module(REPO, "arch", "jamba")
REF = harness.load_module(REPO, "reference", "jamba")
DECODE = 6          # tokens decoded after each prompt
ROWS = 144

# Both sides compute in float32 from the same float32 weights: they differ
# by summation order alone (the program's chunked associative scan against
# the reference's token-by-token recurrence, XLA's dot tiling against
# HIGHEST-precision matmuls), measured below 1e-6 on these logits (mean
# magnitude about 0.1). The same prefill in the configuration's bf16
# (weights, activations, conv state) is off by 2e-2 to 3e-2, two hundred
# times the tolerance.
ATOL = 1e-4


def _smoke(dtype: str):
    from repro.configs import get_arch
    return dataclasses.replace(get_arch("jamba2-3b", smoke=True), dtype=dtype)


def hf_config(mc, dtype: str) -> dict:
    """The published keys of a program config, as a configuration file
    states them."""
    m = mc.mamba
    return {
        "name": mc.name, "model_type": "jamba", "reference": "jamba",
        "num_hidden_layers": mc.n_layers, "hidden_size": mc.d_model,
        "num_attention_heads": mc.n_heads,
        "num_key_value_heads": mc.n_kv_heads,
        "attn_layer_period": mc.attn_every,
        "attn_layer_offset": mc.attn_offset, "num_experts": 1,
        "intermediate_size": mc.d_ff, "mamba_d_state": m.d_state,
        "mamba_d_conv": m.d_conv, "mamba_expand": m.expand,
        "mamba_dt_rank": m.dt_rank, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "vocab_size": mc.vocab,
        "tie_word_embeddings": mc.tie_embeddings,
        "rms_norm_eps": mc.norm_eps, "sliding_window": None,
        "torch_dtype": dtype}


def test_bench_jamba_weights_are_the_programs_parameters():
    """Every leaf the architecture module makes is a parameter of the
    program's model at the same shape and dtype, and the program config
    it gives is the smoke config's architecture."""
    from repro.models.transformer import LM
    mc = _smoke("bfloat16")
    cfg = hf_config(mc, "bfloat16")
    prog = ARCH.program_config(cfg)
    assert dataclasses.replace(prog, mamba=mc.mamba, remat=mc.remat,
                               name=mc.name) == mc
    made = jax.eval_shape(lambda: ARCH.make_params(cfg, 3))
    want = jax.eval_shape(lambda: LM(mc).init(jax.random.PRNGKey(0))[0])
    assert {k: (v.shape, v.dtype) for k, v in made.items()} == \
        {k: (v.shape, v.dtype) for k, v in want.items()}


@pytest.fixture(scope="module")
def served():
    """The smoke model on float32 weights from the module, an engine
    arena of two slots, and the reference's config."""
    from repro.launch.engine import Engine
    from repro.models.transformer import LM
    mc = _smoke("float32")
    cfg = hf_config(mc, "float32")
    params = ARCH.make_params(cfg, 2**31 + 7)
    lm = LM(mc)
    eng = Engine(lm, params, None, max_slots=2, max_seq=ROWS)
    return lm, params, eng, cfg


@pytest.mark.parametrize("S", [37, 100, 130])
def test_bench_jamba_prefill_and_arena_decode_match_reference(served, S):
    """Prompt lengths that are not multiples of the smoke's 16-token scan
    chunk (37, 100 and 130 leave 5, 4 and 2 in the last chunk); the
    request sits in slot 1 of the arena, beside another at a different
    position in slot 0, and decodes teacher-forced."""
    lm, params, eng, cfg = served
    seq = np.random.default_rng(S).integers(0, cfg["vocab_size"],
                                            S + DECODE, dtype=np.int32)
    want = np.asarray(REF.logits(params, cfg, seq[None]))[0]

    row = lm.init_cache(1, ROWS, dtype=jnp.float32)
    pre, row = jax.jit(lm.prefill)(params, None, row, jnp.asarray(seq[None, :S]))
    np.testing.assert_allclose(np.asarray(pre)[0], want[:S], atol=ATOL,
                               rtol=0)
    other = lm.init_cache(1, ROWS, dtype=jnp.float32)
    _, other = jax.jit(lm.prefill)(params, None, other,
                                   jnp.asarray(seq[None, :9] + 1))
    caches = eng._insert(lm.init_cache(2, ROWS, dtype=jnp.float32), other,
                         jnp.int32(0))
    caches = eng._insert(caches, row, jnp.int32(1))
    step = jax.jit(lm.decode_step)
    for t in range(S, S + DECODE):
        tok = jnp.asarray([[0], [seq[t]]], jnp.int32)
        pos = jnp.asarray([9 + t - S, t], jnp.int32)
        lg, caches = step(params, None, caches, tok, pos)
        np.testing.assert_allclose(np.asarray(lg)[1, 0], want[t], atol=ATOL,
                                   rtol=0, err_msg=f"position {t}")


# ----------------------------------------------------- AI21-Jamba2-3B
def _cfg():
    with open(os.path.join(REPO, "bench", "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def _req(prompt_len, out_len):
    return {"prompt": np.zeros(prompt_len, np.int32),
            "tokens": np.zeros(out_len, np.int32)}


# by hand: 26 Mamba layers (in_proj 2560 x 2 x 5120, x_proj 5120 x (160 +
# 2 x 16), dt_proj 160 x 5120, out_proj 5120 x 2560), 2 MQA layers (wq /
# wo 2560 x 2560, wk / wv 2560 x 128), 28 MLPs (3 x 2560 x 8192) and the
# tied head (65536 x 2560)
J_MAMBA = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
J_ATTN = 2 * 2560 * 2560 + 2 * 2560 * 128
J_MLP = 3 * 2560 * 8192
J_HEAD = 65536 * 2560
J_STATE = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)


def test_bench_jamba_arena_is_the_programs():
    """A KV row is the 2 attention layers' one head of K and V in bf16; a
    slot adds the 26 Mamba layers' float32 h and bf16 conv state, which
    is the one slot of the arena the program allocates; the reasoning
    mix's budget holds 192 slots of 9216 rows."""
    from repro.models.transformer import LM
    cfg = _cfg()
    assert ARCH.kv_row_bytes(cfg) == 2 * 2 * 1 * 128 * 2 == 1024
    assert ARCH.state_bytes(cfg) == J_STATE == 9318400
    assert ARCH.slot_bytes(cfg, 9216) == 9216 * 1024 + J_STATE == 18755584
    lm = LM(ARCH.program_config(cfg))
    caches = jax.eval_shape(lambda: lm.init_cache(1, 9216))
    assert sum(a.size * a.dtype.itemsize for a in caches.values()) == \
        18755584
    mix = traffic.load_traffic(REPO, "reasoning_decode")
    assert traffic.arena(mix, lambda n: ARCH.slot_bytes(cfg, n)) == \
        (192, 9216)


def test_bench_jamba_decode_counts():
    """One token whose step attends 101 keys: 2 FLOPs a projection weight
    (the head once), 4 x q_dim a key in each attention layer, 4 x Di x N
    in each Mamba layer; its bytes are every weight once, the attended
    rows, and the Mamba state read and written."""
    cfg = _cfg()
    w = ARCH.served_widths(cfg)
    ctx = {"config": cfg, "widths": w, "requests": [_req(100, 2)],
           "stats": {"decode_tokens": 1, "decode_steps": 1}}
    n = 26 * J_MAMBA + 2 * J_ATTN + 28 * J_MLP
    assert ARCH.matmul_params(w) == {"layers": n, "head": J_HEAD}
    assert ARCH.weight_bytes(ctx) == 2 * (n + J_HEAD) == 6052249600
    assert ARCH.decode_flops(ctx) == \
        2 * (n + J_HEAD) + 4 * 2560 * 2 * 101 + 4 * 5120 * 16 * 26
    # norms (f32), conv_w / conv_b / dt_bias (bf16), dt / B / C norms,
    # A_log and D (f32) of the 26 Mamba layers, and the final norm
    other = (28 * 2 * 2560 * 4 + 2560 * 4
             + 26 * ((4 * 5120 + 5120 + 5120) * 2
                     + (160 + 16 + 16 + 5120 * 16 + 5120) * 4))
    assert ARCH.other_bytes(w) == other
    assert ARCH.decode_bytes(ctx) == \
        2 * (n + J_HEAD) + other + 101 * 1024 + 2 * J_STATE
    shapes = jax.eval_shape(lambda: ARCH.make_params(cfg, 5))
    assert sum(v.size * v.dtype.itemsize for v in shapes.values()) == \
        2 * (n + J_HEAD) + other


# ------------------------------------------------------ a tiny Jamba cell
CELL = "tiny-jamba.reasoning"


@pytest.fixture
def jamba_root(tmp_path):
    """A tiny checkout with the cell `tiny-jamba.reasoning` added by new
    files and entries only: a two-layer Jamba (attention at offset 1 of
    a period of two) whose prompts, 70 and 100 tokens, end in a short
    chunk of the program's 64-token scan."""
    root = make_tiny_root(tmp_path, window=False)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(DATA, "tiny-jamba.json"),
                os.path.join(bench, "configs", "tiny-jamba.json"))
    shutil.copy(os.path.join(DATA, "tiny_reasoning.json"),
                os.path.join(bench, "traffic", "tiny_reasoning.json"))
    add_cell(root, "tiny-jamba", CELL, "tiny_reasoning")
    return root


def _run(root, seed=5, seconds=0.5, control=False):
    spec = harness.load_spec(root, CELL)
    return harness.run_cell(spec, seed, seconds, False, time.time(),
                            log=lambda m: None, control=control)


def test_bench_tiny_jamba_cell_runs_correct(jamba_root):
    spec = harness.load_spec(jamba_root, CELL)
    arch, cfg = spec["arch"], spec["config"]
    from repro.models.transformer import LM
    lm = LM(arch.program_config(cfg))
    caches = jax.eval_shape(lambda: lm.init_cache(1, 112))
    assert arch.slot_bytes(cfg, 112) == \
        sum(a.size * a.dtype.itemsize for a in caches.values())
    res = _run(jamba_root, seed=2**31 + 3)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["token", "state_unchanged"])
def test_bench_tiny_jamba_broken_timed_path_is_not_correct(
        jamba_root, monkeypatch, fault):
    """A stale cache here is the Mamba state as well as the KV rows."""
    _plant(monkeypatch, fault)
    res = _run(jamba_root)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_bench_tiny_jamba_fp8_control_is_not_correct(jamba_root):
    """The reference with fp8 matmul operands, in the program's place,
    comes out not correct on every seed tried, where the program's own
    run on the same sample passes (the limit, 0.015, lies between the
    program's widest gap over eight seeds, 0.0029, and the control's
    narrowest, 0.041)."""
    for seed in (1, 2, 3):
        res = _run(jamba_root, seed=seed, seconds=0.3, control=True)
        assert res["correct"] is False, res["checks"]
        assert harness.verdict(res["program_checks"]), res["program_checks"]
