"""The traffic generator: deterministic from the seed, the stated ladders
and distributions, the same work for every seed, and a new mix found by
its file name alone."""
import json
import math
import os
from collections import Counter

import numpy as np
import pytest

from benchlib import harness, traffic
from conftest import REPO


def _mix(name):
    return traffic.load_traffic(REPO, name)


def test_bench_generators_are_deterministic_from_the_seed():
    mix = _mix("batch_decode")
    big = 2**31 + 12345
    a = traffic.block(mix, big, 0, 92544)
    b = traffic.block(mix, big, 0, 92544)
    assert [q["max_new"] for q in a] == [q["max_new"] for q in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    c = traffic.block(mix, big + 1, 0, 92544)
    assert [q["max_new"] for q in a] != [q["max_new"] for q in c]


@pytest.mark.parametrize("index", [0, 7])
def test_bench_every_seed_gets_the_same_work(index):
    mix = _mix("batch_decode")
    def work(seed):
        reqs = traffic.block(mix, seed, index, 92544)
        return (Counter(q["prompt"].size for q in reqs),
                Counter(q["max_new"] for q in reqs))
    assert work(1) == work(2**33 + 7)


def test_bench_ladder_shares_and_lognormal_quantiles():
    mix = _mix("batch_decode")
    n = 400
    got = Counter(traffic.ladder_lengths(mix["prompt_len"], n))
    for v, p in zip(mix["prompt_len"]["ladder"], mix["prompt_len"]["probs"]):
        assert abs(got[v] - p * n) <= 1
    outs = traffic.lognormal_lengths(mix["output_len"], n)
    spec = mix["output_len"]
    assert min(outs) >= spec["min"] and max(outs) <= spec["max"]
    assert abs(np.median(outs) - spec["median"]) <= 1
    # the medians the mix's source states
    prompts = traffic.ladder_lengths(mix["prompt_len"], n)
    assert np.median(prompts) == mix["stated"]["prompt_median"]
    assert spec["median"] == mix["stated"]["output_median"]
    # the lognormal's quartiles, unclipped there
    q1, q3 = np.percentile(outs, [25, 75])
    z = 0.6744897501960817
    assert abs(q1 - spec["median"] * math.exp(-z * spec["sigma"])) <= 2
    assert abs(q3 - spec["median"] * math.exp(z * spec["sigma"])) <= 2


def test_bench_arena_fits_the_longest_request():
    mix = _mix("batch_decode")
    mult = mix["arena"]["slot_multiple"]
    for kv_row in (98304, 49152):
        slots, rows = traffic.arena(mix, lambda n: n * kv_row)
        assert rows >= traffic.longest_rows(mix)
        assert slots % mult == 0
        assert slots * rows * kv_row <= mix["arena"]["kv_bytes"]


def test_bench_new_traffic_file_is_found_by_name(tiny_root):
    """A mix and a cell added as a file and an entry, with no edit of the
    harness, resolve and generate."""
    with open(os.path.join(tiny_root, "bench", "traffic", "tiny_batch.json")) as f:
        mix = json.load(f)
    mix["prompt_len"] = {"ladder": [4, 12], "probs": [0.25, 0.75]}
    with open(os.path.join(tiny_root, "bench", "traffic", "tiny_new.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.new", "config": "tiny",
                               "traffic": "tiny_new", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.batch" in m.get("workloads", []):
            m["workloads"].append("tiny.new")
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    spec = harness.load_spec(tiny_root, "tiny.new")
    assert spec["traffic"]["prompt_len"]["ladder"] == [4, 12]
    assert {m["name"] for m in spec["end_to_end"]} == {"decode_tok_s",
                                                       "setup_s"}
    reqs = traffic.block(spec["traffic"], 1, 0, 512)
    assert Counter(q["prompt"].size for q in reqs) == {4: 1, 12: 3}
