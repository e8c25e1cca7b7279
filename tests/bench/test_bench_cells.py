"""Whole runs of tiny cells on the CPU, past the harness's look for a
chip: each cell comes out correct with its metrics, and a metric or a
configuration with engine options added as a file and an entry is read."""
import json
import os
import time

import pytest

from benchlib import harness
from conftest import add_cell


def _run(root, cell, seed=5, seconds=0.5):
    spec = harness.load_spec(root, cell)
    return harness.run_cell(spec, seed, seconds, False, time.time(),
                            log=lambda m: None)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.batch", {"decode_tok_s", "setup_s"}),
    ("tiny-b4.batch", {"decode_tok_s", "setup_s"}),
    ("tiny-win.batch", {"decode_tok_s", "setup_s"}),
])
def test_bench_tiny_cell_runs_correct(tiny_root, cell, metrics):
    res = _run(tiny_root, cell, seed=2**31 + 3)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]


def test_bench_metric_added_as_a_file_is_read(tiny_root):
    with open(os.path.join(tiny_root, "bench", "metrics",
                           "requests_done.py"), "w") as f:
        f.write('def read(ctx):\n'
                '    return float(sum(r["finished"] for r in ctx["requests"]))\n')
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["end_to_end"].append({"name": "requests_done", "unit": "requests",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny.batch"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res = _run(tiny_root, "tiny.batch")
    assert res["metrics"]["requests_done"]["value"] == res["attempted"]


def _add_config(root, name, base, **extra):
    """A configuration and its cell added as a file and entries only."""
    with open(os.path.join(root, "bench", "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name=name, **extra)
    with open(os.path.join(root, "bench", "configs", name + ".json"),
              "w") as f:
        json.dump(cfg, f)
    cell = f"{name}.batch"
    add_cell(root, name, cell)
    return cell


@pytest.mark.parametrize("engine,kv_row", [
    # 2 layers x 2 KV heads x (K, V) x (32 int8 codes + a float32 step)
    ({"paged": True, "page_size": 4, "kv_bits": 8}, 2 * 2 * 2 * (32 + 4)),
    # bf16 rows, prompts prefilled in chunks of 8 between decode steps
    ({"prefill_chunk": 8}, 2 * 2 * 2 * 64),
])
def test_bench_engine_options_come_from_the_config_file(tiny_root, engine,
                                                        kv_row):
    cell = _add_config(tiny_root, "tiny-opt", "tiny", engine=engine)
    spec = harness.load_spec(tiny_root, cell)
    assert spec["arch"].kv_row_bytes(spec["config"]) == kv_row
    seen = {}
    from repro.launch import engine as E
    init = E.Engine.__init__

    def spy(self, *a, **k):
        init(self, *a, **k)
        seen.update(paged=self.paged, kv_bits=self.kv_bits,
                    scheduler=type(self.scheduler).__name__)

    E.Engine.__init__ = spy
    try:
        res = _run(tiny_root, cell)
    finally:
        E.Engine.__init__ = init
    assert res["correct"], res["checks"]
    assert seen["paged"] == engine.get("paged", False)
    assert seen["kv_bits"] == engine.get("kv_bits")
    assert (seen["scheduler"] == "ChunkedPrefillScheduler") == \
        ("prefill_chunk" in engine)
