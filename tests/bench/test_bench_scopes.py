"""The decode window's device time by part of the model, and the metrics
read from the program's scopes and counters: on hand-made traces and
contexts whose answers are known, on a tiny cell run on the CPU, and on
300 ms of a trace recorded on one TPU v5e chip."""
import gzip
import json
import os

import pytest

from conftest import DATA, REPO

from benchlib import harness, scopes

MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ARCH = harness.load_module(REPO, "arch", "internlm2")
CONFIG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
          "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
          "vocab_size": 256}


def _op(name, start_ms, dur_ms, scope, program="jit__decode_window"):
    return [name, start_ms * MS, dur_ms * MS, scope, program]


STEP = "jit(_decode_window)/while/body/closed_call/decode_step/while/body"
HAND = {"device": {"/device:TPU:0": [
    _op("%while.1", 0, 75, ""),                    # holds the next 9
    _op("%fusion.1", 10, 20, STEP + "/proj/dot_general:"),
    _op("%flash_decode.1", 30, 6,
        STEP + "/attn_core/flash_decode/pallas_call:"),
    _op("%reshape.1", 36, 4, STEP + "/attn_core/reshape:"),
    _op("%dus.1", 40, 5, STEP + "/kv_write/dynamic_update_slice:"),
    _op("%fusion.3", 45, 12, STEP + "/squeeze:"),  # unscoped in the step
    _op("%fusion.4", 57, 3, STEP + "/pointwise/rsqrt:"),
    _op("%copy.1", 60, 4, ""),                     # no scope: XLA's copy
    _op("%copy.2", 64, 4, "jit(_decode_window)/while:"),  # a borrowed one
    _op("%argmax.1", 68, 4, "jit(_decode_window)/while/body/argmax:"),
    _op("%fusion.2", 95, 10, STEP + "/proj/dot_general:"),  # clipped
    _op("%fusion.9", 76, 10, "jit(_prefill)/proj/dot_general:",
        "jit__prefill(3)"),                        # another program
]}, "host": [["bench.window", 0, 100 * MS, {}]], "profile_start_ns": 0}


def test_bench_parts_of_a_hand_made_trace():
    p = scopes.parts(HAND)
    assert p == pytest.approx({"proj": 0.025, "flash_decode": 0.006,
                               "attn_core": 0.004, "kv_write": 0.005,
                               "pointwise": 0.003, "decode_step": 0.012,
                               "unscoped": 0.008, "window": 0.004})
    assert scopes.parts(HAND, "_prefill")["proj"] == pytest.approx(0.010)


def test_bench_leaves_are_the_ops_that_hold_none():
    ops = HAND["device"]["/device:TPU:0"]
    names = [o[0] for o in scopes.leaves(ops)]
    assert "%while.1" not in names and len(names) == len(ops) - 1


@pytest.mark.parametrize("scope,part", [
    (STEP + "/proj/dot_general:", "proj"),
    (STEP + "/attn_core/proj/x:", "proj"),         # the innermost counts
    (STEP + "/kv_write/vmap(vmap())/scatter:", "kv_write"),
    (STEP + "/attn_core/flash_decode/pallas_call:", "flash_decode"),
    (STEP + "/attn_core/flash_decode_paged/pallas_call:", "flash_decode"),
    (STEP + "/attn_core/reshape:", "attn_core"),
    (STEP + "/closed_call/reshape;proj/reshape:", "proj"),   # fused names
    (STEP + "/closed_call/rsqrt:", "decode_step"),
    (STEP + "/closed_call/pointwise/rsqrt:", "pointwise"),
    ("", "unscoped"),
    ("jit(_decode_window)/while/body/argmax:", "window"),
])
def test_bench_part_of_a_scope(scope, part):
    assert scopes.part_of(scope) == part


@pytest.mark.parametrize("op,scope,part", [
    # XLA's copies of the loop-carried arena, with or without a scope
    ("%copy.62", "", "unscoped"),
    ("%copy.62", "jit(_decode_window)/while:", "unscoped"),
    ("%copy-start.3", "jit(_decode_window)/while/body:", "unscoped"),
    # a copy XLA adds inside the step or a part stays there
    ("%copy.5", STEP + "/dynamic_slice:", "decode_step"),
    ("%copy.7", STEP + "/proj/dot_general:", "proj"),
    ("%copy_fusion.2", "jit(_decode_window)/while/body/argmax:", "window"),
])
def test_bench_part_of_a_copy(op, scope, part):
    assert scopes.part_of(scope, op) == part


# A serialized XSpace as a TPU trace lays it out: the scope is a stat of
# the op's event metadata, here once as a string and once interned.
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[2] fusion()"
    stats { metadata_id: 10
            str_value: "jit(_decode_window)/decode_step/proj/dot:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[2] fusion()"
    stats { metadata_id: 11 int64_value: 96 }
    stats { metadata_id: 10 ref_value: 12 } } }
  event_metadata { key: 3 value { id: 3 name: "jit__decode_window(7)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.3 = bf16[2] copy()" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "bytes_accessed" } }
  stat_metadata { key: 12 value { id: 12
    name: "jit(_decode_window)/decode_step/kv_write/scatter:" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000
             stats { metadata_id: 1 int64_value: 4 } } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.prefill" } }
  stat_metadata { key: 1 value { id: 1 name: "rid" } } }
planes { id: 3 name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 123456789 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } } }
"""


def test_bench_extract_reads_scopes_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ev = scopes.extract(str(path))
    ops = ev["device"]["/device:TPU:0"]
    assert [(o[0], o[3], o[4]) for o in ops] == [
        ("%fusion.1", "jit(_decode_window)/decode_step/proj/dot:",
         "jit__decode_window(7)"),
        ("%fusion.2", "jit(_decode_window)/decode_step/kv_write/scatter:",
         "jit__decode_window(7)"),
        ("%copy.3", "", "jit__decode_window(7)")]
    assert [o[2] for o in ops] == [5000, 1000, 1000]
    assert ev["host"][1][0] == "engine.prefill"
    assert ev["host"][1][3] == {"rid": 4}
    assert ev["profile_start_ns"] == 123456789
    assert scopes.parts(ev) == pytest.approx({
        "proj": 5e-6, "flash_decode": 0.0, "attn_core": 0.0,
        "kv_write": 1e-6, "pointwise": 0.0, "decode_step": 0.0,
        "unscoped": 1e-6, "window": 0.0})


# Two programs whose ops share a name under different scopes: HLO op
# names are numbered per program, so only the metadata id tells them
# apart.
XSPACE_SHARED_NAME = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = bf16[2] fusion()"
    stats { metadata_id: 10 str_value: "jit(_prefill)/proj/dot:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = bf16[2] fusion()"
    stats { metadata_id: 10
            str_value: "jit(_decode_window)/decode_step/kv_write/dus:" } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } } }
"""


def test_bench_extract_tells_ops_of_one_name_apart(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE_SHARED_NAME))
    ops = scopes.extract(str(path))["device"]["/device:TPU:0"]
    assert [(o[0], o[3]) for o in ops] == [
        ("%fusion.7", "jit(_prefill)/proj/dot:"),
        ("%fusion.7", "jit(_decode_window)/decode_step/kv_write/dus:"),
        ("%fusion.7", "jit(_prefill)/proj/dot:")]


def _ctx(**kw):
    parts = {"proj": 0.4, "flash_decode": 0.1, "attn_core": 0.05,
             "kv_write": 0.05, "pointwise": 0.05, "decode_step": 0.25,
             "unscoped": 0.1, "window": 0.0}
    ctx = {"parts": parts, "peaks": PEAKS, "arch": ARCH, "config": CONFIG,
           "widths": ARCH.served_widths(CONFIG),
           "trace_stats": {"decode_steps": 1000,
                           "kv_rows_attended": 5_000_000},
           "stats": {"decode_steps": 96, "decode_dispatches": 4,
                     "dispatch_s": 0.5, "engine_s": 5.0},
           "window_s": 10.0}
    ctx.update(kw)
    return ctx


def _read(name, ctx):
    return harness.load_module(REPO, "metrics", name).read(ctx)


def test_bench_scope_metrics_on_a_hand_made_context():
    ctx = _ctx()
    assert _read("kv_move_share.batch", ctx) == pytest.approx(40.0)
    # a row: 2 layers x 1 KV head x (K, V) x 4 bf16 values
    assert _read("flash_decode_roofline.batch", ctx) == pytest.approx(
        100 * (2 * 1 * 2 * 4 * 2) * 5_000_000 / (0.1 * 819e9))
    # bf16 weights: 2 x (wq 8x8, wk/wv 8x4, wo 8x8, gate/up 8x16,
    # down 16x8) and the head 8x256
    assert _read("proj_roofline.batch", ctx) == pytest.approx(
        100 * 2 * (2 * 576 + 2048) * 1000 / (0.4 * 819e9))
    assert _read("decode_window_steps.batch", ctx) == pytest.approx(24.0)
    assert _read("host_dispatch_share.batch", ctx) == pytest.approx(10.0)
    # the engine's own time, not the window's: a traced run's window also
    # holds the profiler writing its trace, while the engine waits
    longer = _ctx(window_s=30.0)
    assert _read("host_dispatch_share.batch", longer) == pytest.approx(10.0)


@pytest.mark.parametrize("name,ctx", [
    # a program without the scopes or counters (the parent of this change)
    ("kv_move_share.batch", _ctx(parts=None)),
    ("flash_decode_roofline.batch", _ctx(trace_stats=None)),
    ("flash_decode_roofline.batch",
     _ctx(trace_stats={"decode_steps": 10})),
    ("proj_roofline.batch", _ctx(parts=dict.fromkeys(
        ("proj", "flash_decode", "attn_core", "kv_write", "pointwise",
         "decode_step", "unscoped", "window"), 0.0))),
    ("proj_roofline.batch", _ctx(peaks=None)),
    ("decode_window_steps.batch", _ctx(stats={"decode_steps": 9})),
    ("decode_window_steps.batch",
     _ctx(stats={"decode_steps": 0, "decode_dispatches": 0})),
    ("host_dispatch_share.batch", _ctx(stats={"decode_steps": 9})),
    ("host_dispatch_share.batch", _ctx(stats={"dispatch_s": 0.5})),
])
def test_bench_scope_metrics_read_nothing_without_their_source(name, ctx):
    assert _read(name, ctx) is None


def test_bench_counter_metrics_of_a_tiny_cell(tiny_root):
    """The engine's counters reach the readers as the harness hands them
    over (deltas over the window). The harness reads per-layer metrics
    only in a traced run, which needs a chip: the closed loop is driven
    here as it drives it."""
    from benchlib import serve, traffic
    spec = harness.load_spec(tiny_root, "tiny.batch")
    cfg, mix, arch = spec["config"], spec["traffic"], spec["arch"]
    slots, rows = traffic.arena(mix, lambda n: arch.slot_bytes(cfg, n))
    eng = serve.build_engine(arch, cfg, arch.make_params(cfg, 7), slots,
                             rows)
    serve.warm(eng, mix, cfg["vocab_size"])
    base = dict(eng.stats)
    out = serve.closed_loop(eng, mix, 7, 0.5, cfg["vocab_size"],
                            serve.Spans(False))
    ctx = {"stats": {k: v - base.get(k, 0)
                     for k, v in out["stats"].items()},
           "window_s": out["window_s"]}
    st = ctx["stats"]
    steps = _read("decode_window_steps.batch", ctx)
    assert 1 <= steps <= 32 and steps == st["decode_steps"] / st[
        "decode_dispatches"]
    assert 0 < st["dispatch_s"] <= st["engine_s"] <= out["window_s"]
    share = _read("host_dispatch_share.batch", ctx)
    assert share == pytest.approx(100 * st["dispatch_s"] / st["engine_s"])


def _chip_trace():
    """300 ms of a trace recorded on one TPU v5e chip
    (`internlm2-1.8b.batch_decode`, this program's scopes on), as
    `scopes.extract` gives it, kept packed: op starts as deltas in time
    order, scope and program paths as indices into tables. `traced`
    holds the parts and the engine's counters over the traced 5 s it
    was cut from."""
    with gzip.open(os.path.join(DATA, "trace_v5e_dense_scopes.json.gz"),
                   "rt") as f:
        d = json.load(f)
    ops, t = [], 0
    for name, dt, dur, si, pi in d["ops"]:
        t += dt
        ops.append([name, t, dur, d["scopes"][si], d["programs"][pi]])
    ev = {"device": {d["plane"]: ops}, "host": d["host"],
          "profile_start_ns": d["profile_start_ns"]}
    return ev, d["traced"]


def test_bench_parts_of_a_chip_trace():
    from benchlib import trace_reduce
    ev, traced = _chip_trace()
    p = scopes.parts(ev)
    assert set(p) == set(traced["parts"])
    for k in ("proj", "flash_decode", "attn_core", "kv_write", "pointwise",
              "decode_step", "unscoped"):
        assert p[k] > 0, k
    # leaves never overlap: their time is the union of their intervals
    ops = [o for o in ev["device"]["/device:TPU:0"]
           if "_decode_window" in o[4]]
    t1 = ev["host"][0][2]
    spans = [(max(o[1], 0), min(o[1] + o[2], t1))
             for o in scopes.leaves(ops) if o[1] < t1 and o[1] + o[2] > 0]
    union = sum(b - a for a, b in trace_reduce._merge(spans)) / 1e9
    assert sum(p.values()) == pytest.approx(union, rel=1e-6)
    assert union <= t1 / 1e9
    # the slice reads like the stretch it was cut from
    share = [scopes.kv_move_share({"parts": q}) for q in (p, traced["parts"])]
    assert abs(share[0] - share[1]) < 15
    assert scopes.parts(ev, "_prefill")["proj"] > 0


def test_bench_scope_metrics_of_a_chip_trace():
    """The three trace metrics on the parts and counters of the traced
    stretch, as first read on the chip."""
    from benchlib.peaks import peaks_for
    _, traced = _chip_trace()
    with open(os.path.join(REPO, "bench", "configs",
                           "internlm2-1.8b.json")) as f:
        cfg = json.load(f)
    ctx = {"parts": traced["parts"], "trace_stats": traced["trace_stats"],
           "peaks": peaks_for("TPU v5 lite"), "arch": ARCH, "config": cfg,
           "widths": ARCH.served_widths(cfg)}
    kv = _read("kv_move_share.batch", ctx)
    fd = _read("flash_decode_roofline.batch", ctx)
    pj = _read("proj_roofline.batch", ctx)
    assert kv == pytest.approx(62.47, abs=0.01)
    assert fd == pytest.approx(42.01, abs=0.01)
    assert pj == pytest.approx(98.31, abs=0.01)
    p = traced["parts"]
    rest = 100 * (p["proj"] + p["flash_decode"] + p["attn_core"]
                  + p["pointwise"] + p["window"]) / sum(p.values())
    assert kv + rest == pytest.approx(100.0)
