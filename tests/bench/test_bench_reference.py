"""The benchmark's plain reference agrees with the program's own model
(`LM.prefill` and cached `LM.decode_step`) on the benchmark's weights, at
a size the CPU holds, dense and as a packed artifact; its unpacking
inverts the benchmark's packing and agrees with the program's."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness, serve, weights
from conftest import DATA, REPO

ARCH = harness.load_module(REPO, "arch", "internlm2")


def _ref():
    return harness.load_module(REPO, "reference", "internlm2")


def _cfg(name, dtype="float32"):
    with open(os.path.join(DATA, name + ".json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = dtype
    return cfg


def _f32(params):
    return {k: (v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v)
            for k, v in params.items()}


@pytest.mark.parametrize("name", ["tiny", "tiny-b4"])
def test_bench_reference_matches_prefill_and_decode(name):
    """Prefill logits at every prompt position and the first cached
    decode step's logits: program (float32 activations) vs reference."""
    ref = _ref()
    cfg = _cfg(name)
    params = _f32(ARCH.make_params(cfg, 7))
    eng = serve.build_engine(ARCH, cfg, params, slots=1, rows=32)
    lm = eng.lm
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg["vocab_size"], 12).astype(np.int32)
    nxt = 5
    cache = lm.init_cache(1, 32, dtype=jnp.float32)
    logits, cache = lm.prefill(params, None, cache, jnp.asarray(prompt)[None])
    dlog, _ = lm.decode_step(params, None, cache, jnp.asarray([[nxt]]),
                             jnp.asarray([prompt.size], jnp.int32))
    seq = np.concatenate([prompt, [nxt]])[None]
    h = ref.hidden(params, cfg, seq)
    head = {k: v for k, v in params.items() if k.startswith("head")}
    want = ref._head_logits(h, jnp.arange(seq.shape[1])[None],
                            params["final_norm"], head,
                            D=cfg["hidden_size"],
                            eps=float(cfg["rms_norm_eps"]), mode="f32")
    got = np.concatenate([np.asarray(logits[0]), np.asarray(dlog[0])])
    want = np.asarray(want[0])
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= 2e-3 * scale
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_bench_reference_unpack_inverts_pack():
    from repro.core.quant import unpack_codes
    ref = _ref()
    rng = np.random.default_rng(0)
    for bits in (2, 4, 8):
        q = (1 << (bits - 1)) - 1
        codes = rng.integers(-q, q + 1, (37, 5)).astype(np.int32)
        words = weights.pack(jnp.asarray(codes), bits)
        assert words.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ref.unpack(words, bits, 37)),
                                      codes)
        np.testing.assert_array_equal(
            np.asarray(unpack_codes(words, bits, 37, axis=0)), codes)


def test_bench_served_gap_is_zero_on_reference_argmax():
    """Tokens the reference itself would pick have gap 0; a token it
    ranks lower has its gap below the best."""
    ref = _ref()
    cfg = _cfg("tiny", "bfloat16")
    params = ARCH.make_params(cfg, 11)
    prompt = np.arange(1, 9, dtype=np.int32)
    toks = []
    seq = list(prompt)
    for _ in range(4):
        h = ref.hidden(params, cfg, np.asarray(seq, np.int32)[None])
        head = {k: v for k, v in params.items() if k.startswith("head")}
        lg = ref._head_logits(h, jnp.asarray([[len(seq) - 1]]),
                              params["final_norm"], head,
                              D=cfg["hidden_size"],
                              eps=float(cfg["rms_norm_eps"]), mode="f32")
        t = int(jnp.argmax(lg[0, 0]))
        toks.append(t)
        seq.append(t)
    req = {"prompt": prompt, "tokens": np.asarray(toks, np.int32)}
    gaps = ref.served_gaps(params, cfg, [req], rows=32)[0]
    assert gaps.shape == (4,)
    assert np.max(gaps) < 1e-4
    bad = dict(req, tokens=np.asarray(toks[:3] + [(toks[3] + 1) % 512],
                                      np.int32))
    assert ref.served_gaps(params, cfg, [bad], rows=32)[0][3] > 1e-3
