"""The architecture modules (`bench/arch/<model_type>.py`): InternLM2's
gives the weights, arenas and counts the benchmark had before they moved
there, each module counts the arena the program allocates, and a second
architecture is added to a checkout with new files and entries only."""
import hashlib
import json
import os
import time

import jax
import numpy as np
import pytest

from benchlib import harness, traffic
from conftest import DATA, REPO, add_window_arch, make_tiny_root

ARCH = harness.load_module(REPO, "arch", "internlm2")


def _cfg(path):
    with open(path) as f:
        return json.load(f)


def _checksum(params) -> str:
    h = hashlib.sha256()
    for k in sorted(params):
        a = np.asarray(params[k])
        for part in (k, str(a.shape), str(a.dtype)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,digest", [
    ("tiny", "2636e6afa4db5b2d5d1496aafa86422425e2c5f76abf48cafc5c00b7cc200c94"),
    ("tiny-b4",
     "a69535e852028d3acdfcc6fc3c9ca0ac3ffa8f2f1ea34cbc5b0c0a3221102a73"),
])
def test_bench_internlm2_weights_are_bitwise_the_same(name, digest):
    """Every name, shape, dtype and bit of the weights one seed makes, as
    `weights.make_params` made them before it moved to the module."""
    params = ARCH.make_params(_cfg(os.path.join(DATA, name + ".json")),
                              2**31 + 11)
    assert _checksum(params) == digest


# name: (served widths, bytes a KV row holds, (slots, rows) under the
# batch_decode budget, number and bytes of the weights' leaves, bytes one
# step's weights hold, FLOPs and bytes of the request set below at 300
# steps)
REAL = {
    "internlm2-1.8b": (
        {"L": 24, "D": 2048, "dh": 128, "Vp": 92672, "H": 16, "KVh": 8,
         "F": 8192, "bits": None}, 98304, (12, 2560), (12, 3779469312),
        3399483392, 2439784693760.0, 1141704228864.0),
    "internlm2-1.8b-geta-s50-b4": (
        {"L": 24, "D": 2048, "dh": 128, "Vp": 92672, "H": 8, "KVh": 4,
         "F": 4096, "bits": 4}, 49152, (28, 2560), (20, 852370084),
        472383488, 1342498144256.0, 202644652032.0),
}
REQUESTS = [(366, 129), (2048, 512), (1020, 8), (671, 1)]


@pytest.mark.parametrize("name", sorted(REAL))
def test_bench_real_configs_read_as_before(name):
    """Widths, arena, weight shapes and counts of the two chip
    configurations, as the benchmark read them before the module held
    them; the weights by shape alone (nothing is allocated)."""
    widths, row, arena, leaves, wbytes, fl, by = REAL[name]
    cfg = _cfg(os.path.join(REPO, "bench", "configs", name + ".json"))
    assert ARCH.served_widths(cfg) == widths
    assert ARCH.kv_row_bytes(cfg) == row
    assert ARCH.slot_bytes(cfg, 2560) == 2560 * row
    mix = traffic.load_traffic(REPO, "batch_decode")
    assert traffic.arena(mix, lambda n: ARCH.slot_bytes(cfg, n)) == arena
    shapes = jax.eval_shape(lambda: ARCH.make_params(cfg, 5))
    assert (len(shapes), sum(v.size * v.dtype.itemsize
                             for v in shapes.values())) == leaves
    assert shapes["embed"].shape == (92672, 2048)
    reqs = [{"prompt": np.zeros(p, np.int32), "tokens": np.zeros(o, np.int32)}
            for p, o in REQUESTS]
    ctx = {"config": cfg, "widths": widths, "requests": reqs,
           "stats": {"decode_tokens": sum(o - 1 for _, o in REQUESTS),
                     "decode_steps": 300}}
    assert ARCH.weight_bytes(ctx) == wbytes
    assert ARCH.decode_flops(ctx) == fl
    assert ARCH.decode_bytes(ctx) == by


def _arena_slot_bytes(arch, cfg, rows) -> int:
    """Device bytes of one slot of the arena the program allocates for
    the configuration, by shape."""
    from repro.models.transformer import LM
    lm = LM(arch.program_config(cfg))
    plan = arch.slim_plan(cfg, lm)
    if plan is not None:
        lm.apply_slim_plan(plan)
    caches = jax.eval_shape(lambda: lm.init_cache(1, rows))
    return sum(a.size * a.dtype.itemsize for a in caches.values())


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny-b4.batch",
                                  "tiny-win.batch"])
def test_bench_slot_bytes_are_the_programs_arena(tiny_root, cell):
    spec = harness.load_spec(tiny_root, cell)
    arch, cfg = spec["arch"], spec["config"]
    for rows in (16, 32):
        assert arch.slot_bytes(cfg, rows) == \
            _arena_slot_bytes(arch, cfg, rows)


@pytest.mark.parametrize("name", sorted(REAL))
def test_bench_slot_bytes_are_the_programs_arena_at_full_width(name):
    cfg = _cfg(os.path.join(REPO, "bench", "configs", name + ".json"))
    assert ARCH.slot_bytes(cfg, 2560) == _arena_slot_bytes(ARCH, cfg, 2560)


def _files(root) -> dict:
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_bench_architecture_added_as_files_only(tmp_path):
    """The `window_gqa` architecture (sliding-window attention, a ring
    arena) joins a checkout through new files and appended entries: no
    file that was there changes, and its cell runs correct with as many
    slots as its ring's bytes allow."""
    root = make_tiny_root(tmp_path, window=False)
    before = _files(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench_before = json.load(f)
    add_window_arch(root)
    after = _files(root)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(before) == {
        os.path.join("bench", "arch", "window_gqa.py"),
        os.path.join("bench", "reference", "window_gqa.py"),
        os.path.join("bench", "configs", "tiny-win.json")}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for key in ("configs", "workloads"):
        n = len(bench_before[key])
        assert bench[key][:n] == bench_before[key]
        assert len(bench[key]) == n + 1
    for key in ("end_to_end", "per_layer"):
        for old, new in zip(bench_before[key], bench[key]):
            cells = old.get("workloads")
            assert new == (old if cells is None
                           else dict(old, workloads=cells + ["tiny-win.batch"]))

    spec = harness.load_spec(root, "tiny-win.batch")
    cfg, mix, arch = spec["config"], spec["traffic"], spec["arch"]
    window = cfg["sliding_window"]
    # the ring wraps: the longest request outgrows the window, and every
    # prompt fits it (prefill writes the prompt in one piece)
    assert max(mix["prompt_len"]["ladder"]) <= window \
        < traffic.longest_rows(mix) <= mix["arena"]["rows"]
    assert arch.program_config(cfg).window == window
    rows = mix["arena"]["rows"]
    full = traffic.arena(mix, lambda n: n * arch.kv_row_bytes(cfg))
    ring = traffic.arena(mix, lambda n: arch.slot_bytes(cfg, n))
    assert (full, ring) == ((4, rows), (6, rows))
    res = harness.run_cell(spec, 2**31 + 5, 0.5, False, time.time(),
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
