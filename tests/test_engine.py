"""Continuous-batching engine: one-shot prefill parity and engine-vs-static
decode parity.

Two invariants keep the engine honest:
1. `LM.prefill` (one full-sequence forward that fills the caches) must be
   numerically interchangeable with the sequential decode-step prefill —
   same logits, same caches, same greedy tokens — dense AND compressed.
2. The engine's continuous-batching decode (per-slot positions, admission/
   eviction, slot cache arena) must emit token-identical output to the
   static lockstep `serve_loop` for the same request set.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.subnet import prepare_serving
from repro.launch.engine import Engine, build_engine, synthetic_prompts
from repro.launch.serve import serve_loop
from repro.models.transformer import LM

ARCH = "internlm2-1.8b"


def _serving_lm(arch=ARCH, compressed=False, quantized=True):
    cfg = get_arch(arch, smoke=True)
    if cfg.dtype != "float32":      # tight parity needs f32 weights
        cfg = dataclasses.replace(cfg, dtype="float32")
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    params, qparams, _ = prepare_serving(
        lm, params, quantized=quantized, compressed=compressed)
    return lm, params, qparams


def _sequential_prefill(lm, params, qparams, toks, max_seq):
    """The reference cache-building path: one decode_step per token."""
    caches = lm.init_cache(toks.shape[0], max_seq, dtype=jnp.float32)
    step = jax.jit(lm.decode_step)
    logits = []
    for p in range(toks.shape[1]):
        lg, caches = step(params, qparams, caches, toks[:, p:p + 1],
                          jnp.int32(p))
        logits.append(lg)
    return jnp.concatenate(logits, axis=1), caches


# ------------------------------------------------------------ prefill parity
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["dense", "compressed"])
def test_prefill_matches_sequential_decode(compressed):
    lm, params, qparams = _serving_lm(compressed=compressed)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0, lm.cfg.vocab)
    lg_seq, c_seq = _sequential_prefill(lm, params, qparams, toks, 16)
    c_pre = lm.init_cache(2, 16, dtype=jnp.float32)
    lg_pre, c_pre = jax.jit(lm.prefill)(params, qparams, c_pre, toks)

    assert np.array_equal(np.argmax(np.asarray(lg_pre), -1),
                          np.argmax(np.asarray(lg_seq), -1))
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(lg_seq),
                               rtol=1e-4, atol=1e-4)
    for k in c_seq:
        np.testing.assert_allclose(np.asarray(c_pre[k]), np.asarray(c_seq[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


@pytest.mark.parametrize("arch", ["rwkv6-3b", "jamba-1.5-large-398b"])
def test_prefill_matches_sequential_decode_stateful_families(arch):
    """SSM/RWKV/hybrid(+MoE) caches are recurrent states, not KV rows — the
    one-shot prefill must leave exactly the state S sequential steps
    would. MoE routing must not drop prompt tokens (one-token decode never
    overflows an expert, so a dropping prefill silently diverges)."""
    lm, params, qparams = _serving_lm(arch, quantized=False)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, lm.cfg.vocab)
    lg_seq, c_seq = _sequential_prefill(lm, params, qparams, toks, 16)
    c_pre = lm.init_cache(2, 16, dtype=jnp.float32)
    lg_pre, c_pre = jax.jit(lm.prefill)(params, qparams, c_pre, toks)
    assert np.array_equal(np.argmax(np.asarray(lg_pre), -1),
                          np.argmax(np.asarray(lg_seq), -1))
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(lg_seq),
                               rtol=1e-4, atol=1e-4)
    for k in c_seq:
        np.testing.assert_allclose(np.asarray(c_pre[k]), np.asarray(c_seq[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


# ----------------------------------------------------- engine vs serve_loop
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["dense", "compressed"])
def test_engine_matches_static_serve_loop(compressed):
    """Acceptance: continuous-batching decode emits token-identical output
    to the static lockstep loop for the same request set — with fewer
    slots than requests, so admission/eviction runs mid-decode."""
    batch, prompt_len, gen = 3, 6, 8
    eng, lm = build_engine(ARCH, True, compressed=compressed,
                           max_slots=2, max_seq=prompt_len + gen)
    prompts = synthetic_prompts(lm.cfg, [prompt_len] * batch)
    # identical requests by construction: the static loop consumes the
    # same prompt matrix the engine was fed
    seq = serve_loop(ARCH, True, batch, prompt_len, gen,
                     compressed=compressed, verbose=False,
                     prompts=np.stack(prompts))
    for p in prompts:
        eng.submit(p, gen)
    out = eng.run()
    assert sorted(out) == [0, 1, 2]
    for rid in out:
        np.testing.assert_array_equal(out[rid], np.asarray(seq)[rid],
                                      err_msg=f"request {rid}")
    # eviction freed slots for the queued third request
    assert eng.stats["evicted"] == batch
    assert eng.stats["decode_steps"] > gen - 1   # two waves of decode


def test_engine_mixed_lengths_match_per_request_reference():
    """Slots at different positions share one decode dispatch; each
    request's tokens must match its own single-request static decode."""
    lm, params, qparams = _serving_lm()
    lens = [7, 3, 5, 4]
    gens = [6, 9, 4, 7]
    prompts = synthetic_prompts(lm.cfg, lens)
    eng = Engine(lm, params, qparams, max_slots=2, max_seq=16)
    for p, g in zip(prompts, gens):
        eng.submit(p, g)
    out = eng.run()

    step = jax.jit(lm.decode_step)
    for rid, (p, g) in enumerate(zip(prompts, gens)):
        caches = lm.init_cache(1, 16, dtype=jnp.float32)
        toks = jnp.asarray(p)[None]
        for q in range(len(p)):
            lg, caches = step(params, qparams, caches, toks[:, q:q + 1],
                              jnp.int32(q))
        ref = [int(jnp.argmax(lg[0, -1]))]
        for q in range(g - 1):
            tok = jnp.asarray([[ref[-1]]], jnp.int32)
            lg, caches = step(params, qparams, caches, tok,
                              jnp.int32(len(p) + q))
            ref.append(int(jnp.argmax(lg[0, -1])))
        np.testing.assert_array_equal(out[rid], np.asarray(ref, np.int32),
                                      err_msg=f"request {rid}")


def test_engine_slot_reuse_isolated():
    """A request admitted into a freed slot must decode exactly as if it
    had the slot from the start — no state bleeds through eviction."""
    lm, params, qparams = _serving_lm()
    prompts = synthetic_prompts(lm.cfg, [5, 5, 5])
    alone = Engine(lm, params, qparams, max_slots=1, max_seq=16)
    rid = alone.submit(prompts[2], 6)
    want = alone.run()[rid]

    eng = Engine(lm, params, qparams, max_slots=1, max_seq=16)
    for p in prompts:
        eng.submit(p, 6)
    out = eng.run()
    np.testing.assert_array_equal(out[2], want)


def test_engine_admission_guards():
    lm, params, qparams = _serving_lm()
    eng = Engine(lm, params, qparams, max_slots=2, max_seq=8)
    with pytest.raises(ValueError):
        eng.submit(np.arange(6), 4)     # needs 6 + 4 - 1 = 9 rows > 8
    with pytest.raises(ValueError):
        eng.submit(np.arange(3), 0)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,)), 2)
    # one-token request completes at admission, never holding a slot
    rid = eng.submit(np.arange(4), 1)
    out = eng.run()
    assert len(out[rid]) == 1
    assert eng.stats["decode_steps"] == 0


def test_engine_admits_exact_capacity_request():
    """A request needing exactly max_seq cache rows must be admitted: S
    prompt rows plus N-1 decode writes touch rows [0, S+N-1) — the first
    generated token comes from the prefill and writes nothing. The old
    `S + N > max_seq` guard rejected this boundary request (off-by-one),
    silently shrinking every engine's usable budget by one token."""
    lm, params, qparams = _serving_lm()
    prompts = synthetic_prompts(lm.cfg, [5])
    eng = Engine(lm, params, qparams, max_slots=1, max_seq=8)
    rid = eng.submit(prompts[0], 4)     # rows needed: 5 + 4 - 1 = 8 == 8
    out = eng.run()
    assert len(out[rid]) == 4
    # and the boundary decode is trustworthy: identical to a roomy arena
    big = Engine(lm, params, qparams, max_slots=1, max_seq=16)
    brid = big.submit(prompts[0], 4)
    np.testing.assert_array_equal(out[rid], big.run()[brid])


def test_engine_admission_guards_one_past_capacity():
    lm, params, qparams = _serving_lm()
    prompts = synthetic_prompts(lm.cfg, [5])
    eng = Engine(lm, params, qparams, max_slots=1, max_seq=8)
    with pytest.raises(ValueError):
        eng.submit(prompts[0], 5)       # 5 + 5 - 1 = 9 rows > 8


def test_run_drains_only_new_completions():
    """A reused engine must not re-report earlier batches (or retain them:
    `done` is released at each drain)."""
    lm, params, qparams = _serving_lm()
    prompts = synthetic_prompts(lm.cfg, [4, 4])
    eng = Engine(lm, params, qparams, max_slots=2, max_seq=16)
    r0 = eng.submit(prompts[0], 3)
    assert set(eng.run()) == {r0}
    r1 = eng.submit(prompts[1], 3)
    assert set(eng.run()) == {r1}
    assert not eng.done


def test_draft_prefill_time_rides_its_own_counters(monkeypatch):
    """The draft arena's admission prefill is draft work: its wall time
    and token count must land in draft_prefill_* — folding it into
    prefill_s (as it used to) inflated the target prefill denominator
    and corrupted prefill_tok_per_s for every speculative serve. A fake
    clock that ticks 1.0 per call makes every timed block weigh exactly
    its clock reads less one (entry, before and after each dispatch, end
    of the wait), so the split is assertable without real timing."""
    import itertools
    import types

    import repro.launch.engine as engine_mod
    eng, lm = build_engine(ARCH, True, speculative=True, draft_k=2,
                           max_slots=2, max_seq=16)
    prompts = synthetic_prompts(lm.cfg, [5, 7])
    for p in prompts:
        eng.submit(p, 4)
    eng.warmup()
    ticks = itertools.count()
    tick = lambda: float(next(ticks))
    monkeypatch.setattr(engine_mod, "time",
                        types.SimpleNamespace(time=tick, perf_counter=tick))
    eng.run()
    s = eng.stats
    assert s["prefills"] == 2 and s["prefill_tokens"] == 12
    assert s["draft_prefills"] == 2 and s["draft_prefill_tokens"] == 12
    # one timed block each per admission — target and draft prefill time
    # no longer pool into one counter: the target's one dispatch weighs
    # 3.0, the draft's two (prefill, insert) 5.0
    assert s["prefill_s"] == pytest.approx(2 * 3.0)
    assert s["draft_prefill_s"] == pytest.approx(2 * 5.0)


def test_kv_bytes_counts_both_arenas():
    """kv_bytes() is the headline 'KV HBM this serve pins' stat: a
    speculative engine's draft arena is pinned HBM too, so excluding it
    (the old behavior) under-reported every --speculative serve."""
    from repro.core.subnet import tree_bytes
    eng, _ = build_engine(ARCH, True, speculative=True, max_slots=2,
                          max_seq=16)
    t, d = tree_bytes(eng.caches), tree_bytes(eng.dcaches)
    assert d > 0
    assert eng.kv_bytes() == t + d
    assert eng.serving_meta["kv_bytes"] == eng.kv_bytes()
    assert eng.kv_pool_bytes() == eng.kv_bytes()   # contiguous: no pool gap
    non, _ = build_engine(ARCH, True, max_slots=2, max_seq=16)
    assert non.kv_bytes() == tree_bytes(non.caches)


def test_one_token_request_does_not_stall_the_queue():
    """A request that completes at admission must hand its slot to the
    next queued request in the same round — on a single slot, draining
    [1-token, 8-token] used to raise 'queue stuck with no active slots'."""
    lm, params, qparams = _serving_lm()
    prompts = synthetic_prompts(lm.cfg, [4, 4, 4])
    eng = Engine(lm, params, qparams, max_slots=1, max_seq=16)
    rids = [eng.submit(prompts[0], 1), eng.submit(prompts[1], 8),
            eng.submit(prompts[2], 1)]
    out = eng.run()
    assert [len(out[r]) for r in rids] == [1, 8, 1]


# ------------------------------------------------ the arena decoded in place
INPLACE_MODES = {
    "dense": {},
    "pruned_packed": dict(packed=True, bits_init=4.0, prune_sparsity=0.5),
    "windowed": {},
    "speculative": {},
}


def _sequential_tokens(lm, params, qparams, prompt, gen, max_seq):
    """One request alone through `decode_step`, a token at a time."""
    step = jax.jit(lm.decode_step)
    caches = lm.init_cache(1, max_seq, dtype=jnp.float32)
    toks = jnp.asarray(prompt)[None]
    for q in range(len(prompt)):
        lg, caches = step(params, qparams, caches, toks[:, q:q + 1],
                          jnp.int32(q))
    out = [int(jnp.argmax(lg[0, -1]))]
    for q in range(gen - 1):
        lg, caches = step(params, qparams, caches,
                          jnp.asarray([[out[-1]]], jnp.int32),
                          jnp.int32(len(prompt) + q))
        out.append(int(jnp.argmax(lg[0, -1])))
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("mode", sorted(INPLACE_MODES))
def test_inplace_arena_token_identity(mode):
    """On the stacked (nb, B, S, KVh*dh) arena that decode writes and
    reads in place, the engine with the flash-decode kernel (interpreted)
    is token-identical to the engine on the xla-ref oracle and to each
    request decoded alone through `decode_step`: dense, pruned + packed
    b4, a windowed ring that wraps, and a speculative engine whose draft
    arena decodes in place too."""
    from repro.kernels import use_backend
    from repro.launch.speculative import build_draft
    cfg = dataclasses.replace(get_arch(ARCH, smoke=True), dtype="float32",
                              window=6 if mode == "windowed" else 0)
    lm = LM(cfg)
    ckpt, _ = lm.init(jax.random.PRNGKey(0))
    params, qparams, _ = prepare_serving(lm, ckpt, **INPLACE_MODES[mode])
    prompts = synthetic_prompts(cfg, [5, 3, 6])
    gens = [7, 4, 6]

    def serve(backend):
        draft = (build_draft(ARCH, True, ckpt, sparsity=0.5, bits=8.0)
                 if mode == "speculative" else None)
        eng = Engine(lm, params, qparams, max_slots=2, max_seq=16,
                     draft=draft, draft_k=2)
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        with use_backend(backend):
            return eng.run()

    kernel, oracle = serve("pallas-interpret"), serve("xla-ref")
    assert sorted(kernel) == sorted(oracle) == [0, 1, 2]
    for rid, (p, g) in enumerate(zip(prompts, gens)):
        np.testing.assert_array_equal(kernel[rid], oracle[rid])
        np.testing.assert_array_equal(
            oracle[rid], _sequential_tokens(lm, params, qparams, p, g, 16),
            err_msg=f"{mode}: request {rid}")


def test_arena_is_held_once():
    """The slot insert takes the arena over (the arrays handed to it are
    deleted), and after a decode window exactly one arena's worth of
    attention-cache arrays is live."""
    import gc
    lm, params, qparams = _serving_lm()
    eng = Engine(lm, params, qparams, max_slots=3, max_seq=21)
    for p in synthetic_prompts(lm.cfg, [5, 4]):
        eng.submit(p, 6)
    kv = sorted(lm.kv_heads())
    handed = [eng.caches[k] for k in kv]
    assert eng._admit() == 2
    assert all(a.is_deleted() for a in handed)
    del handed
    assert eng._window()
    gc.collect()
    shapes = {tuple(eng.caches[k].shape) for k in kv}
    live = [a for a in jax.live_arrays() if tuple(a.shape) in shapes]
    assert {id(a) for a in live} == {id(eng.caches[k]) for k in kv}


def test_warmup_holds_one_arena_between_lengths():
    """Each window length warm-up compiles and runs drops the arena it
    returns before the next: when a length runs, the engine's arena is
    the only one live (the one it computes is the second). Holding the
    last call's arena made three, which a 3.6 GB Jamba2-3B arena beside
    6 GB of weights does not leave room for on one 16 GB chip."""
    import gc
    lm, params, qparams = _serving_lm()
    eng = Engine(lm, params, qparams, max_slots=5, max_seq=23)
    kv = sorted(lm.kv_heads())
    shapes = {tuple(eng.caches[k].shape) for k in kv}
    gc.collect()        # arrays of earlier tests in this process
    window, live = eng._decode_window, []

    def counted(*args):
        live.append(sum(tuple(a.shape) in shapes for a in jax.live_arrays()))
        return window(*args)

    eng._decode_window = counted
    eng.warmup()
    assert live == [len(kv)] * len(eng.warmed_window_ks())
