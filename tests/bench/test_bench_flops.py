"""FLOP and byte counts against a count by hand, and the peaks table."""
import json
import os

import pytest

from benchlib import flops, peaks, weights
from conftest import REPO


def _widths(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return weights.served_widths(json.load(f))


def test_bench_decode_flops_match_hand_count():
    w = _widths("internlm2-1.8b")
    # per layer: wq 2048x2048, wk/wv 2048x1024, wo 2048x2048,
    # gate/up 2048x8192, down 8192x2048; head 2048 x 92672 (padded vocab)
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 8192)
    head = 2048 * 92672
    assert flops.matmul_params(w) == {"layers": 24 * per_layer, "head": head}
    # one token at context 100: 2 per weight, 4 * q_dim per key per layer
    want = 2 * (24 * per_layer + head) + 4 * 2048 * 24 * 100
    assert flops.decode_flops(w, 1, 100) == want
    assert flops.weight_bytes(w) == 2 * (24 * per_layer + head)


def test_bench_packed_artifact_counts():
    w = _widths("internlm2-1.8b-geta-s50-b4")
    per_layer = (2048 * 1024 + 2 * 2048 * 512 + 1024 * 2048
                 + 3 * 2048 * 4096)
    n = 24 * per_layer + 2048 * 92672
    assert flops.weight_bytes(w) == n // 2
    assert flops.decode_flops(w, 3, 0) == 2 * n * 3


def test_bench_context_sum():
    # prompt 10, 4 output tokens: decode steps attend 11, 12, 13 keys
    assert flops.decode_context_sum(10, 4) == 11 + 12 + 13
    assert flops.decode_context_sum(10, 1) == 0


def test_bench_decode_bytes():
    w = _widths("internlm2-1.8b")
    kv = weights.kv_bytes_per_row({"num_hidden_layers": 24, "hidden_size": 2048,
                                   "head_dim": 128, "vocab_size": 92544,
                                   "num_attention_heads": 16,
                                   "num_key_value_heads": 8,
                                   "intermediate_size": 8192})
    assert kv == 2 * 24 * 8 * 128 * 2
    assert flops.decode_bytes(w, 2, 1000, kv) == \
        2 * flops.weight_bytes(w) + 1000 * kv


def test_bench_peaks_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2**30
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
