"""FLOP and byte counts of the InternLM2 architecture module against a
count by hand, the shared counting helpers, and the peaks table."""
import json
import os

import numpy as np
import pytest

from benchlib import flops, harness, peaks
from conftest import REPO

ARCH = harness.load_module(REPO, "arch", "internlm2")


def _cfg(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _ctx(name, tokens=0, steps=0, requests=()):
    cfg = _cfg(name)
    return {"config": cfg, "widths": ARCH.served_widths(cfg),
            "stats": {"decode_tokens": tokens, "decode_steps": steps},
            "requests": list(requests)}


def _req(prompt_len, out_len):
    return {"prompt": np.zeros(prompt_len, np.int32),
            "tokens": np.zeros(out_len, np.int32)}


def test_bench_decode_flops_match_hand_count():
    ctx = _ctx("internlm2-1.8b", tokens=1, requests=[_req(100, 2)])
    w = ctx["widths"]
    # per layer: wq 2048x2048, wk/wv 2048x1024, wo 2048x2048,
    # gate/up 2048x8192, down 8192x2048; head 2048 x 92672 (padded vocab)
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 8192)
    head = 2048 * 92672
    assert ARCH.matmul_params(w) == {"layers": 24 * per_layer, "head": head}
    # one token, its step attending 101 keys: 2 per weight, 4 * q_dim per
    # key per layer
    want = 2 * (24 * per_layer + head) + 4 * 2048 * 24 * 101
    assert ARCH.decode_flops(ctx) == want
    assert ARCH.count_flops(w, 1, 100) == want - 4 * 2048 * 24
    assert ARCH.weight_bytes(ctx) == 2 * (24 * per_layer + head)


def test_bench_packed_artifact_counts():
    ctx = _ctx("internlm2-1.8b-geta-s50-b4", tokens=3)
    per_layer = (2048 * 1024 + 2 * 2048 * 512 + 1024 * 2048
                 + 3 * 2048 * 4096)
    n = 24 * per_layer + 2048 * 92672
    assert ARCH.weight_bytes(ctx) == n // 2
    assert ARCH.decode_flops(ctx) == 2 * n * 3


def test_bench_context_sum():
    # prompt 10, 4 output tokens: decode steps attend 11, 12, 13 keys
    assert flops.decode_context_sum(10, 4) == 11 + 12 + 13
    assert flops.decode_context_sum(10, 1) == 0
    # a window of 12 keys caps each step's
    assert flops.decode_context_sum(10, 4, window=12) == 11 + 12 + 12
    assert flops.context_sum([_req(10, 4), _req(3, 0), _req(5, 2)]) == \
        11 + 12 + 13 + 6


def test_bench_decode_bytes():
    ctx = _ctx("internlm2-1.8b", steps=2, requests=[_req(1000, 2)])
    kv = ARCH.kv_row_bytes(ctx["config"])
    assert kv == 2 * 24 * 8 * 128 * 2
    assert ARCH.slot_bytes(ctx["config"], 2560) == 2560 * kv
    assert ARCH.decode_bytes(ctx) == \
        2 * ARCH.weight_bytes(ctx) + 1001 * kv


def test_bench_peaks_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2**30
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
