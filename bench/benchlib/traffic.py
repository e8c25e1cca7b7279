"""The one traffic generator. A mix is a data file,
`bench/traffic/<mix>.json`, found by its name; this module reads it and
turns it into requests.

Requests come in blocks of `block`. Every block of every seed holds the
same multiset of sizes, in another order: prompt lengths are split over
the ladder by largest remainder, and output lengths are the clipped
lognormal's quantiles at (i + 0.5) / block. The seed picks the order, the
pairing of prompt with output length, and the token ids. So the work of a
run does not change with its seed, and two seeds differ only in what a
real queue would reorder.

Kinds of mix:
  closed_loop   the next block is submitted whenever fewer than
                `queue_per_slot` x slots requests wait, so the slots never
                run dry
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

KINDS = ("closed_loop",)


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "bench", "traffic", name + ".json")


def load_traffic(root: str, name: str) -> dict:
    with open(traffic_path(root, name)) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name}: kind {mix.get('kind')!r} is not "
                         f"one of {KINDS}")
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, *stream])


def ladder_lengths(spec: dict, n: int) -> list[int]:
    """n prompt lengths over `spec["ladder"]` in the shares `spec["probs"]`
    (largest remainder, ties to the shorter length)."""
    ladder, probs = list(spec["ladder"]), np.asarray(spec["probs"], float)
    if len(ladder) != len(probs) or abs(probs.sum() - 1.0) > 1e-6:
        raise ValueError(f"ladder {ladder} and probs {list(probs)} do not "
                         f"pair up or do not sum to 1")
    exact = probs * n
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return [int(v) for v, c in zip(ladder, counts) for _ in range(c)]


def lognormal_lengths(spec: dict, n: int) -> list[int]:
    """n output lengths: quantiles of a lognormal with `median` and
    `sigma`, clipped to [min, max]."""
    nd = NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def sizes(mix: dict, n: int, rng: np.random.Generator) -> list[tuple[int, int]]:
    """n (prompt length, output length) pairs, shuffled by `rng`."""
    prompts = ladder_lengths(mix["prompt_len"], n)
    outs = lognormal_lengths(mix["output_len"], n)
    prompts = [prompts[i] for i in rng.permutation(n)]
    outs = [outs[i] for i in rng.permutation(n)]
    return list(zip(prompts, outs))


def prompt_tokens(rng: np.random.Generator, length: int, vocab: int
                  ) -> np.ndarray:
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def longest_rows(mix: dict) -> int:
    """Cache rows the longest request of the mix needs: its prompt, and
    every output token but the last."""
    return max(mix["prompt_len"]["ladder"]) + mix["output_len"]["max"] - 1


def block(mix: dict, seed: int, index: int, vocab: int) -> list[dict]:
    """Block `index` of a mix: `block` requests, each
    {"prompt": ids, "max_new": n}."""
    rng = rng_for(seed, 1, index)
    return [{"prompt": prompt_tokens(rng, s, vocab), "max_new": m}
            for s, m in sizes(mix, int(mix["block"]), rng)]


def arena(mix: dict, slot_bytes) -> tuple[int, int]:
    """(slots, rows) of the KV arena: rows from the mix, slots as many as
    the mix's KV byte budget holds, rounded down to `slot_multiple`, where
    one slot of `rows` rows takes `slot_bytes(rows)` device bytes (the
    architecture module's count)."""
    a = mix["arena"]
    rows = int(a["rows"])
    if rows < longest_rows(mix):
        raise ValueError(f"arena rows {rows} < the {longest_rows(mix)} the "
                         f"longest request needs")
    per_slot = int(slot_bytes(rows))
    mult = int(a.get("slot_multiple", 8))
    slots = int(a["kv_bytes"]) // per_slot // mult * mult
    if slots < 1:
        raise ValueError(f"KV budget {a['kv_bytes']} B holds no "
                         f"{mult}-slot group of {per_slot} B slots")
    return slots, rows
