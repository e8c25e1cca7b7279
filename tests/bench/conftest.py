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


def make_tiny_root(path) -> str:
    """A checkout holding the benchmark's code and two tiny cells (dense
    and packed closed loops) in place of the real ones, with their
    metrics."""
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
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
