"""Drive the program's serving engine: build it on the benchmark's
weights, warm the cell's shapes, and run the closed loop.

From the program this takes only the system under test: the model
(`repro.models.transformer.LM`, configured by the architecture module
at the served widths), the engine
(`repro.launch.engine.Engine`) and its counters (`Engine.stats`) and
request stamps (`Request.admit_t`, `finish_t`).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np
from jax.profiler import TraceAnnotation

from benchlib import traffic

MARK = 10.0         # seconds between the window's logged token counts


def build_engine(arch, cfg: dict, params: dict, slots: int, rows: int):
    """An engine serving `params` over a (slots, rows) arena, on the
    model the architecture module `arch` configures: its program config,
    and a pruned artifact's slim plan installed first, so prefill, decode
    and the arena run at the served widths. The configuration's `engine`
    group holds the engine's own options (`paged`, `page_size`,
    `kv_bits`, `n_pages`, `prefix_sharing`), and `prefill_chunk` there
    swaps in the chunked-prefill scheduler."""
    from repro.launch.engine import Engine
    from repro.models.transformer import LM
    lm = LM(arch.program_config(cfg))
    plan = arch.slim_plan(cfg, lm)
    if plan is not None:
        lm.apply_slim_plan(plan)
    opts = dict(cfg.get("engine", {}))
    chunk = opts.pop("prefill_chunk", None)
    scheduler = None
    if chunk:
        from repro.launch.scheduler import ChunkedPrefillScheduler
        scheduler = ChunkedPrefillScheduler(chunk=int(chunk))
    return Engine(lm, params, None, max_slots=slots, max_seq=rows,
                  scheduler=scheduler, **opts)


class Spans:
    """Host spans around the engine's calls, on the profiler's clock. Off
    (free) unless `on`; wrapping sets instance attributes only."""

    ENGINE_CALLS = ("run", "step", "submit", "_admit", "_window",
                    "_act_decode", "_finish")
    JITS = ("_prefill", "_insert", "_decode", "_decode_window")

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        return TraceAnnotation(name) if self.on else contextlib.nullcontext()

    def wrap_engine(self, eng) -> None:
        if not self.on:
            return
        for attr in self.ENGINE_CALLS + self.JITS:
            fn = getattr(eng, attr, None)
            if fn is not None:
                setattr(eng, attr, self._wrapped(f"engine.{attr}", fn))

    @staticmethod
    def _wrapped(name, fn):
        def call(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)
        return call


def _submit(eng, prompt, max_new: int):
    eng.submit(prompt, max_new)
    return eng.queue[-1]


def warm(eng, mix: dict, vocab: int) -> None:
    """Compile what the window will run: a prefill per ladder length, the
    slot insert, and every decode window length `Engine.run` drains
    with."""
    rng = traffic.rng_for(0, 9)
    for n in mix["prompt_len"]["ladder"]:
        _submit(eng, traffic.prompt_tokens(rng, n, vocab), 2)
    eng.warmup()
    eng.run()


def closed_loop(eng, mix: dict, seed: int, seconds: float, vocab: int,
                spans: Spans, on_drive=None) -> dict:
    """A closed loop that never runs dry: whenever fewer than
    `queue_per_slot` x slots requests wait, the next block of the mix is
    submitted, and the engine is driven by the iteration `Engine.run`
    loops over (a fused decode window, or `Engine.step` where `run` would
    step), until `seconds` have passed. Returns every request with the
    tokens it had when the window closed, the engine's counters then,
    the window's length, and (time, tokens so far) every `MARK` seconds.
    Requests still in flight are left there."""
    # Engine.run() drains its queue: driving its own iteration lets the
    # queue be topped up in between, so no round tail idles the slots
    drive = (eng.step if (eng.draft is not None or eng._chunk)
             else eng._window)
    depth = int(mix["queue_per_slot"]) * eng.max_slots
    reqs, b, marks = [], 0, []
    t0 = time.time()
    while True:
        if len(eng.queue) < depth:
            with spans("bench.generate"):
                block = traffic.block(mix, seed, b, vocab)
            with spans("bench.submit"):
                for q in block:
                    reqs.append({"req": _submit(eng, q["prompt"],
                                                q["max_new"]),
                                 "prompt": q["prompt"],
                                 "max_new": q["max_new"]})
            b += 1
            continue
        drive()
        elapsed = time.time() - t0
        if elapsed >= MARK * (len(marks) + 1):
            marks.append((elapsed, sum(len(r["req"].tokens) for r in reqs)))
        if on_drive is not None:
            on_drive(elapsed)
        if elapsed >= seconds:
            break
    stats = dict(eng.stats)
    for rec in reqs:
        r = rec["req"]
        rec["tokens"] = np.asarray(r.tokens, np.int32)
        rec["finished"] = r.finish_t > 0
    return {"requests": reqs, "window_s": elapsed, "stats": stats,
            "marks": marks}
