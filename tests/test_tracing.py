"""The serving engine's own measurement: its counters agree with the
requests it served, its spans nest as documented in a profiler trace and
carry their metadata on the profiler's clock, every `pallas_call` has a
stable name, and the decode step's ops carry the model's name scopes."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.engine import build_engine, synthetic_prompts

ARCH = "internlm2-1.8b"
PROMPTS = [5, 9, 3, 7, 6]
BUDGETS = [6, 3, 9, 4, 1]         # the 1-token request never takes a slot


def _engine(**kw):
    eng, lm = build_engine(ARCH, True, max_slots=2, max_seq=32, **kw)
    for p, n in zip(synthetic_prompts(lm.cfg, PROMPTS, seed=1), BUDGETS):
        eng.submit(p, n)
    eng.warmup()
    return eng


def _context_sum(prompt_len: int, n_tokens: int) -> int:
    """Rows a request's decode steps attend: its first token comes from
    the prefill, and the step at position p attends rows [0, p]."""
    return sum(prompt_len + i for i in range(1, n_tokens))


@pytest.mark.parametrize("drive", ["window", "step"])
def test_counters_match_the_requests_served(drive):
    eng = _engine()
    calls = []
    jitted = eng._decode_window

    def recorded(*args):
        calls.append(args[-1])          # the window's static length k
        return jitted(*args)

    eng._decode_window = recorded
    go = eng._window if drive == "window" else eng.step
    while eng.pending:
        go()
    st = eng.stats
    reqs = list(eng.done.values())
    assert len(reqs) == len(PROMPTS)
    assert "admitted" not in st
    assert st["kv_rows_attended"] == sum(
        _context_sum(r.prompt.size, len(r.tokens)) for r in reqs)
    assert st["decode_tokens"] == sum(len(r.tokens) - 1 for r in reqs)
    if drive == "window":
        assert calls and st["decode_dispatches"] == len(calls)
        assert st["decode_steps"] == sum(calls)
    else:
        assert not calls
        assert st["decode_dispatches"] == st["decode_steps"] > 0
    assert 0 < st["dispatch_s"] <= st["engine_s"]
    assert st["decode_s"] + st["prefill_s"] <= st["engine_s"]
    assert st["prefills"] == len(PROMPTS)


def test_speculative_rounds_count_positions_scored():
    eng = _engine(speculative=True, draft_k=2)
    rounds = []
    jitted = eng._spec

    def recorded(*args):
        rounds.append(args[-1])
        return jitted(*args)

    eng._spec = recorded
    while eng.pending:
        eng.step()
    st = eng.stats
    assert st["decode_dispatches"] == st["spec_steps"] == len(rounds)
    assert st["decode_steps"] == sum(k + 1 for k in rounds)
    # a round attends its k+1 scored positions, committed or not
    assert st["kv_rows_attended"] >= sum(
        _context_sum(r.prompt.size, len(r.tokens))
        for r in eng.done.values())


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    start, events = None, []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return start, events


def test_engine_spans_nest_and_carry_metadata(tmp_path):
    eng = _engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.pending:
            eng._window()
    finally:
        jax.profiler.stop_trace()
    start, ev = _host_events(str(tmp_path))
    names = {n for n, *_ in ev}
    for n in ("engine.iteration", "engine.prefill",
              "engine.prefill.dispatch", "engine.prefill.wait",
              "engine.insert", "engine.insert.dispatch", "engine.decode",
              "engine.decode.dispatch", "engine.decode.wait",
              "engine.decode.emit", "engine.finish"):
        assert n in names, n

    def inside(child, parent):
        kids = [e for e in ev if e[0] == child]
        outer = [e for e in ev if e[0] == parent]
        assert kids
        for _, a, b, _ in kids:
            assert any(s <= a and b <= t for _, s, t, _ in outer), child

    for parent in ("engine.prefill", "engine.decode", "engine.insert"):
        inside(parent + ".dispatch", parent)
    inside("engine.prefill.wait", "engine.prefill")
    inside("engine.decode.wait", "engine.decode")
    inside("engine.decode.emit", "engine.decode")
    for name in ("engine.prefill", "engine.decode", "engine.insert"):
        inside(name, "engine.iteration")

    reqs = {r.rid: r for r in eng.done.values()}
    prefill = {m["rid"]: (a, b, m) for n, a, b, m in ev
               if n == "engine.prefill"}
    assert set(prefill) == set(reqs)
    for rid, (a, b, meta) in prefill.items():
        assert meta["prompt_len"] == reqs[rid].prompt.size
        # the admission stamp is on the profiler's clock: the trace's
        # events count from its start time
        stamp = round(reqs[rid].admit_t * 1e9) - start
        assert a - 2e5 <= stamp <= b + 2e5, (rid, a, stamp, b)
    assert {m["rid"] for n, _, _, m in ev if n == "engine.finish"} \
        == set(reqs)
    decode = [m for n, _, _, m in ev if n == "engine.decode"]
    assert sum(m["k"] for m in decode) == eng.stats["decode_steps"]
    assert all(1 <= m["slots"] <= 2 for m in decode)


@pytest.mark.parametrize("kw", [dict(prefill_chunk=4),
                                dict(paged=True, page_size=8)],
                         ids=["chunked", "paged"])
def test_admit_stamp_ends_the_wait_for_the_first_token(tmp_path, kw):
    """On every path a request is stamped admitted when the prefill that
    yields its first token returns: inside the last of its
    `engine.prefill` spans (a chunked prefill has one a chunk)."""
    eng = _engine(**kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.pending:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    start, ev = _host_events(str(tmp_path))
    last = {}
    for n, a, b, m in sorted(ev, key=lambda e: e[1]):
        if n == "engine.prefill":
            last[m["rid"]] = (a, b)
    reqs = list(eng.done.values())
    assert len(reqs) == len(PROMPTS) and set(last) == {r.rid for r in reqs}
    for r in reqs:
        a, b = last[r.rid]
        stamp = round(r.admit_t * 1e9) - start
        assert a - 2e5 <= stamp <= b + 2e5, (r.rid, a, stamp, b)


def test_decode_ops_carry_the_model_scopes():
    eng = _engine()
    tok = jnp.zeros((eng.max_slots, 1), jnp.int32)
    pos = jnp.zeros((eng.max_slots,), jnp.int32)
    hlo = eng._decode_window.lower(eng.params, eng.qparams, eng.caches,
                                   tok, pos, 2).as_text(debug_info=True)
    for scope in ("decode_step/", "/proj/", "/kv_write/", "/attn_core/",
                  "/pointwise/"):
        assert scope in hlo, scope


def _pallas_names(fn, *args):
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found.append(e.params["name"])
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _kernel_cases():
    from repro.kernels import decode_attn, fake_quant, gemm_core
    from repro.core.quant import pack_codes
    x = jnp.ones((8, 64), jnp.float32)
    w = jnp.ones((64, 128), jnp.float32)
    col = jnp.ones((128,), jnp.float32)
    codes = jnp.ones((64, 128), jnp.int32)
    interp = "pallas-interpret"
    q = jnp.ones((2, 2, 2, 128), jnp.float32)
    kv = jnp.ones((2, 64, 2, 128), jnp.float32)
    pos = jnp.array([3, 9], jnp.int32)
    pool = jnp.ones((8, 16, 2, 128), jnp.float32)
    table = jnp.array([[1, 2, 3, 4], [5, 6, 7, 0]], jnp.int32)
    d, qm, t = 0.1, 1.0, 0.0
    return {
        "gemm_core.dense": lambda: _pallas_names(
            lambda a, b: gemm_core.gemm(a, b, backend=interp), x, w),
        "gemm_core.dequant": lambda: _pallas_names(
            lambda a, b: gemm_core.gemm(a, b, (gemm_core.dequant(col),),
                                        backend=interp), x, w),
        "gemm_core.unpack_dequant_b4": lambda: _pallas_names(
            lambda a, b: gemm_core.gemm(
                a, b, (gemm_core.unpack_dequant(4, col),), backend=interp),
            x, pack_codes(codes, 4, axis=0)),
        "gemm_core.fake_quant+col_mask": lambda: _pallas_names(
            lambda a, b: gemm_core.gemm(
                a, b, gemm_core.fq_mask_ops(d, qm, t, col), backend=interp),
            x, w),
        "flash_decode": lambda: _pallas_names(
            lambda a, k, v: decode_attn.decode_attn_pallas(
                a, k, v, pos, interpret=True), q, kv, kv),
        "flash_decode_paged": lambda: _pallas_names(
            lambda a, k, v: decode_attn.paged_decode_attn_pallas(
                a, k, v, pos, table, page_size=16, seq_len=64,
                interpret=True), q, pool, pool),
        "fake_quant": lambda: _pallas_names(
            lambda a: fake_quant.fake_quant_fwd_pallas(
                a, d, qm, t, interpret=True), w),
        "fake_quant_grad": lambda: _pallas_names(
            lambda a: fake_quant.fake_quant_bwd_pallas(
                a, d, qm, t, a, interpret=True), w),
    }


@pytest.mark.parametrize("name", list(_kernel_cases()))
def test_pallas_call_carries_its_name(name):
    assert _kernel_cases()[name]() == [name]
