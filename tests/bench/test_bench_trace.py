"""The reduction from a trace to busy time, top device operations and
idle gaps: on a hand-made trace whose answers are known, and on 300 ms of
a trace recorded on one TPU v5e chip (`internlm2-1.8b.batch_decode`,
events as `extract` gives them, trimmed)."""
import gzip
import json
import os

import pytest

from conftest import DATA

from benchlib import trace_reduce

MS = 1_000_000


def test_bench_trace_reduction_by_hand():
    ev = {"device": {"/device:TPU:0": [
        ["fusion.1", 10 * MS, 20 * MS],        # 10-30
        ["fusion.2", 25 * MS, 10 * MS],        # 25-35, overlaps
        ["dot.3", 60 * MS, 30 * MS],           # 60-90
        ["dot.3", 95 * MS, 20 * MS],           # 95-115, clipped at 100
    ]}, "host": [
        ["bench.window", 0, 100 * MS],
        ["engine.run", 0, 100 * MS],
        ["engine._admit", 36 * MS, 20 * MS],   # covers the 35-60 gap's middle
    ]}
    red = trace_reduce.reduce(ev)
    assert red["window_s"] == pytest.approx(0.1)
    # busy: 10-35, 60-90 and 95-100 -> 60 ms
    assert red["busy_s"] == pytest.approx(0.060)
    assert red["device_ops"][0] == ["dot.3", pytest.approx(0.035)]
    assert red["device_ops"][1] == ["fusion.1", pytest.approx(0.020)]
    gaps = red["idle_gaps"]
    assert gaps[0] == ["engine._admit", pytest.approx(0.025)]
    assert gaps[1] == ["engine.run", pytest.approx(0.010)]     # 0-10
    assert gaps[2] == ["engine.run", pytest.approx(0.005)]     # 90-95
    assert len(gaps) == 3


def test_bench_trace_reduction_needs_the_window_and_a_device():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"device": {"/device:TPU:0": []}, "host": []})
    with pytest.raises(ValueError):
        trace_reduce.reduce({"device": {},
                             "host": [["bench.window", 0, 10]]})



def test_bench_trace_reduction_of_a_chip_trace():
    with gzip.open(os.path.join(DATA, "trace_v5e_dense.json.gz"), "rt") as f:
        ev = json.load(f)
    red = trace_reduce.reduce(ev)
    assert red["window_s"] == pytest.approx(0.3)
    # the union of overlapping (nested) ops: busy within the window, and
    # no more than the time of the ops on the line
    ops = ev["device"]["/device:TPU:0"]
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] <= sum(d for _, _, d in ops) / 1e9
    names = [n for n, _ in red["device_ops"]]
    assert len(names) == trace_reduce.TOP == len(set(names))
    assert all(n.startswith("%") and " = " not in n for n in names)
    secs = [t for _, t in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = red["idle_gaps"]
    assert gaps and all(n.startswith(("engine.", "bench.", "host:"))
                        for n, _ in gaps)
    assert sum(t for _, t in gaps) <= red["window_s"] - red["busy_s"] + 1e-9
