"""With the timed path broken underneath a whole tiny run (past the
harness's look for a chip), `correct` comes out false: a token altered
where the decode produces it, a decode that returns its cache unchanged,
and the fp8 control put in the program's place."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchlib import harness


def _run(root, cell, seed=5, seconds=0.5):
    spec = harness.load_spec(root, cell)
    return harness.run_cell(spec, seed, seconds, False, time.time(),
                            log=lambda m: None)


def _plant(monkeypatch, fault):
    """Break the engine's decode dispatches where they are produced. A
    cache "returned unchanged" is a copy taken before the call, so the
    decode may donate the one it is handed."""
    from repro.launch import engine as E
    init = E.Engine.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        win, one = self._decode_window, self._decode

        def window(params, qparams, caches, tok, pos, k):
            if fault == "state_unchanged":
                before = jax.tree.map(jnp.copy, caches)
                return win(params, qparams, caches, tok, pos, k)[0], before
            toks, new = win(params, qparams, caches, tok, pos, k)
            return toks.at[-1].add(1), new

        def step(params, qparams, caches, tok, pos):
            if fault == "state_unchanged":
                before = jax.tree.map(jnp.copy, caches)
                return one(params, qparams, caches, tok, pos)[0], before
            nxt, new = one(params, qparams, caches, tok, pos)
            return nxt + 1, new

        self._decode_window, self._decode = window, step
    monkeypatch.setattr(E.Engine, "__init__", broken)


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny-b4.batch",
                                  "tiny-win.batch"])
@pytest.mark.parametrize("fault", ["token", "state_unchanged"])
def test_bench_broken_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                cell, fault):
    _plant(monkeypatch, fault)
    res = _run(tiny_root, cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny-b4.batch",
                                  "tiny-win.batch"])
def test_bench_fp8_control_is_not_correct(tiny_root, cell):
    """The reference computed with fp8 matmul operands, put in the
    program's place, comes out not correct on every seed tried, through
    the same decision as the program's run, which passes on the same
    sample."""
    spec = harness.load_spec(tiny_root, cell)
    for seed in (1, 2, 3):
        res = harness.run_cell(spec, seed, 0.3, False, time.time(),
                               log=lambda m: None, control=True)
        assert res["correct"] is False, res["checks"]
        assert res["checks"]["logit_gap"]["value"] > \
            res["checks"]["logit_gap"]["limit"]
        assert harness.verdict(res["program_checks"]), res["program_checks"]
