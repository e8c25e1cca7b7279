"""Step-scheduler policy + disaggregated chunked-prefill tier.

Pins three contracts from the PR-9 refactor:

1. **Policy extraction is behavior-preserving** — `Engine.step()` under
   the default `OneShotScheduler` is the classic admit-then-decode
   iteration (every pre-existing engine test keeps passing); a custom
   policy object can reshape the iteration without engine changes.
2. **Chunked prefill is token-identical to one-shot prefill** — staging
   through `LM.verify_chunk` at absolute positions writes the same KV
   rows the one-shot prefill writes, so greedy decode must not move by
   a single token, across plain/paged/speculative/TP stacks.
3. **Disaggregation actually disaggregates** — decode steps run while a
   prompt is mid-prefill (`decode_steps_mid_prefill`, asserted under a
   fake deterministic clock so the timing stats are exact), and the
   compiled-shape set stays pinned to `chunk_buckets(chunk)`.
"""
import numpy as np
import pytest

from repro.launch.engine import Engine, build_engine, engine_serve
from repro.launch.scheduler import (ChunkedPrefillScheduler,
                                    OneShotScheduler, chunk_buckets,
                                    chunk_plan)

ARCH = "internlm2-1.8b"


# ------------------------------------------------------------ chunk maths
def test_chunk_plan_sums_and_shapes():
    assert chunk_plan(21, 16) == [16, 4, 1]
    assert chunk_plan(16, 16) == [16]
    assert chunk_plan(5, 16) == [4, 1]
    assert chunk_plan(40, 8) == [8, 8, 8, 8, 8]
    assert chunk_plan(1, 16) == [1]
    for s in range(1, 70):
        for c in (1, 3, 8, 16):
            plan = chunk_plan(s, c)
            assert sum(plan) == s
            assert all(x in chunk_buckets(c) for x in plan), (s, c, plan)


def test_chunk_plan_validation():
    with pytest.raises(ValueError):
        chunk_plan(0, 16)
    with pytest.raises(ValueError):
        chunk_plan(8, 0)


def test_chunk_buckets():
    assert chunk_buckets(16) == [1, 2, 4, 8, 16]
    assert chunk_buckets(12) == [1, 2, 4, 8, 12]
    assert chunk_buckets(1) == [1]


def test_chunked_scheduler_validation():
    with pytest.raises(ValueError):
        ChunkedPrefillScheduler(chunk=0)


# --------------------------------------------------------- token identity
@pytest.mark.parametrize("kw", [
    pytest.param({}, id="plain"),
    pytest.param(dict(packed=True, bits_init=4.0), id="packed_b4"),
    pytest.param(dict(paged=True, page_size=8), id="paged"),
    pytest.param(dict(speculative=True, draft_k=4), id="speculative"),
])
def test_chunked_prefill_token_identity(kw):
    base = engine_serve(ARCH, True, [12, 5, 21], 8, verbose=False, **kw)
    got = engine_serve(ARCH, True, [12, 5, 21], 8, verbose=False,
                       prefill_chunk=8, **kw)
    assert sorted(base) == sorted(got)
    for rid in base:
        np.testing.assert_array_equal(base[rid], got[rid])


def test_chunked_prefill_chunk_one_token_identity():
    # chunk=1 degenerates to sequential per-token prefill — the maximally
    # adversarial chunk plan — and must still match one-shot exactly
    base = engine_serve(ARCH, True, [9, 4], 6, verbose=False)
    got = engine_serve(ARCH, True, [9, 4], 6, verbose=False,
                       prefill_chunk=1)
    for rid in base:
        np.testing.assert_array_equal(base[rid], got[rid])


# ------------------------------------------------------- disaggregation
class _FakeTime:
    """Deterministic clock: every time() or perf_counter() call advances
    1 ms. Makes the wall-time stats exact integers of the call count
    instead of host noise, so the interleaving assertions can't flake."""
    def __init__(self):
        self.t = 0.0

    def time(self):
        self.t += 0.001
        return self.t

    perf_counter = time


def test_decode_runs_mid_prefill(monkeypatch):
    import repro.launch.engine as engine_mod
    monkeypatch.setattr(engine_mod, "time", _FakeTime())
    from repro.launch.engine import synthetic_prompts
    eng, lm = build_engine(ARCH, True, max_seq=64, prefill_chunk=4)
    prompts = synthetic_prompts(lm.cfg, [4, 33], seed=0)
    eng.submit(prompts[0], 20)    # short prompt: decoding early
    eng.submit(prompts[1], 8)     # long prompt: 9 chunks of prefill
    eng.warmup()
    out = eng.run()
    assert len(out) == 2
    # the long prompt needed ceil(33/4)=9 chunk dispatches, and request 0
    # decoded while they ran: disaggregation's whole point
    assert eng.stats["prefill_chunks"] >= 9
    assert eng.stats["decode_steps_mid_prefill"] >= 8
    assert eng.stats["chunked_prefills"] == 2
    assert eng.stats["prefills"] == 2
    # fake clock: every timed section reads it at entry, before and
    # after its one dispatch, and at the end of its wait — exactly 3 ms —
    # so the stats are pure call counts, nonzero and exact
    assert eng.stats["prefill_s"] == pytest.approx(
        0.003 * eng.stats["prefill_chunks"])
    assert eng.stats["decode_s"] == pytest.approx(
        0.003 * eng.stats["decode_steps"])
    assert eng.stats["dispatch_s"] == pytest.approx(
        0.001 * (eng.stats["prefill_chunks"] + eng.stats["decode_steps"]
                 + eng.stats["prefills"]))   # + one slot insert each


def test_oneshot_never_decodes_mid_prefill():
    st = {}
    engine_serve(ARCH, True, [12, 5, 21], 8, verbose=False, stats=st)
    assert st["decode_steps_mid_prefill"] == 0
    assert st["prefill_chunks"] == 0
    assert st["chunked_prefills"] == 0


# ----------------------------------------------------- compile-set pinning
def test_chunked_warmup_compile_set_pinned():
    from repro.launch.engine import synthetic_prompts
    eng, lm = build_engine(ARCH, True, max_seq=64, prefill_chunk=8)
    prompts = synthetic_prompts(lm.cfg, [21, 5, 12, 33], seed=0)
    for p in prompts:
        eng.submit(p, 8)
    eng.warmup()
    sizes = eng.compile_cache_sizes()
    assert sizes["_prefill_chunk"] == len(chunk_buckets(8))
    assert sizes["_decode"] == 1
    eng.run()
    # the serve dispatched only warmed shapes: zero recompiles
    after = eng.compile_cache_sizes()
    assert after["_prefill_chunk"] == len(chunk_buckets(8))
    assert after["_decode"] == 1


# --------------------------------------------------------- policy object
def test_default_scheduler_is_oneshot():
    eng, _ = build_engine(ARCH, True)
    assert isinstance(eng.scheduler, OneShotScheduler)
    assert eng.scheduler.plan_step(eng) == ("admit", "decode")
    assert eng._chunk is None


class _DecodeTwice:
    """A custom policy: two decode batches per step. Exists to prove the
    engine executes whatever the policy plans — the extension point the
    refactor bought."""
    chunk = None

    def plan_step(self, eng):
        return ("admit", "decode", "decode")


def test_custom_scheduler_drives_engine():
    from repro.launch.engine import synthetic_prompts
    eng, lm = build_engine(ARCH, True)
    eng.scheduler = _DecodeTwice()
    for p in synthetic_prompts(lm.cfg, [6, 6], seed=0):
        eng.submit(p, 9)
    while eng.pending:
        eng.step()
    assert len(eng.done) == 2
    # two decode batches ran per step(): steps counted them both
    assert eng.stats["decode_steps"] >= 8
    ref = engine_serve(ARCH, True, [6, 6], 9, verbose=False)
    for rid, req_tokens in ((r, eng.done[r].tokens) for r in eng.done):
        np.testing.assert_array_equal(np.asarray(req_tokens, np.int32),
                                      ref[rid])


# ------------------------------------------------------------ gating rails
def test_window_refuses_chunked_engine():
    eng, _ = build_engine(ARCH, True, prefill_chunk=4)
    with pytest.raises(RuntimeError, match="chunked"):
        eng._window()


def test_chunked_refuses_windowed_and_stateful_archs():
    """Chunked prefill stages through verify_chunk, which inherits its
    preconditions: full arenas and attention mixers everywhere. The
    engine must refuse at construction, not corrupt mid-serve."""
    import dataclasses

    import jax

    from repro.configs import get_arch
    from repro.models.transformer import LM
    sched = ChunkedPrefillScheduler(chunk=4)

    cfg = get_arch(ARCH, smoke=True)
    wlm = LM(dataclasses.replace(cfg, window=8))
    wparams, _ = wlm.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="window"):
        Engine(wlm, wparams, None, max_seq=16, scheduler=sched)

    rcfg = get_arch("rwkv6-3b", smoke=True)
    rlm = LM(rcfg)
    rparams, _ = rlm.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention mixers"):
        Engine(rlm, rparams, None, max_seq=16, scheduler=sched)


def test_pending_tracks_staging(monkeypatch):
    eng, lm = build_engine(ARCH, True, prefill_chunk=4)
    assert not eng.pending
    from repro.launch.engine import synthetic_prompts
    eng.submit(synthetic_prompts(lm.cfg, [9], seed=0)[0], 4)
    assert eng.pending
    eng.step()           # chunk 1 of [4, 4, 1] staged, queue empty
    assert not eng.queue and eng._prefill_job is not None
    assert eng.pending   # mid-prefill work must keep run() draining
    while eng.pending:
        eng.step()
    assert len(eng.done) == 1
