"""Fixtures of the benchmark's own tests: the benchmark's code on the
import path, and a throwaway checkout with cells small enough for the
CPU."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "bench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def make_tiny_root(path, window: bool = True) -> str:
    """A checkout holding the benchmark's code and tiny cells in place of
    the real ones, with their metrics: dense and packed closed loops, and
    (with `window`) the `window_gqa` test architecture's, added after the
    others by `add_window_arch`."""
    root = str(path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("tiny", "tiny-b4"):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(root, "bench", "configs", name + ".json"))
    for name in ("tiny_batch",):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(root, "bench", "traffic", name + ".json"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny", "tiny-b4")]
    bench["workloads"] = [
        {"name": "tiny.batch", "config": "tiny", "traffic": "tiny_batch",
         "chips": 1, "why": "test"},
        {"name": "tiny-b4.batch", "config": "tiny-b4",
         "traffic": "tiny_batch", "chips": 1, "why": "test"}]
    batch = ["tiny.batch", "tiny-b4.batch"]

    def metric(name, unit, better, cells, **kw):
        return dict({"name": name, "unit": unit, "better": better,
                     "workloads": cells}, **kw)

    e2e = dict(bound=0.05, source="host_clock")
    bench["end_to_end"] = [
        metric("decode_tok_s", "tokens/s", "higher", batch, **e2e),
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    layer = dict(source="program_counter", layer="test")
    bench["per_layer"] = [
        metric("slot_occupancy.batch", "%", "higher", batch,
               moves="decode_tok_s", **layer),
        metric("decode_mfu.batch", "%", "higher", batch,
               moves="decode_tok_s", **layer),
        metric("device_idle.batch", "%", "lower", batch,
               moves="decode_tok_s", **layer)]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    if window:
        add_window_arch(root)
    return root


def add_cell(root, config: str, cell: str, traffic: str = "tiny_batch"
             ) -> None:
    """Entries for a configuration file and its cell added to a tiny
    checkout's BENCHMARK.json, the cell reporting every metric that
    `tiny.batch` reports."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.batch" in m.get("workloads", []):
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(bench, f)


def add_window_arch(root) -> None:
    """The `window_gqa` test architecture (sliding-window attention on a
    ring arena) and its cell `tiny-win.batch`, added with new files and
    entries only: its architecture module, its reference, its
    configuration file, and BENCHMARK.json entries."""
    bench = os.path.join(root, "bench")
    for src, dst in (("arch_window_gqa.py", "arch/window_gqa.py"),
                     ("reference_window_gqa.py", "reference/window_gqa.py"),
                     ("tiny-win.json", "configs/tiny-win.json")):
        assert not os.path.exists(os.path.join(bench, dst)), dst
        shutil.copy(os.path.join(DATA, src), os.path.join(bench, dst))
    add_cell(root, "tiny-win", "tiny-win.batch")


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
