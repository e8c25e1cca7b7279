"""One run of one cell: resolve it from `BENCHMARK.json` and its files,
make the weights, warm up, measure, check, and print the result line.

A cell is found by name. Its configuration file is the one
`BENCHMARK.json` names; the file's `model_type` names its architecture
module, `bench/arch/<model_type>.py` (weights, program config, arena
bytes, FLOP and byte counts: see `bench/arch/internlm2.py`), and its
`reference` the plain reference, `bench/reference/<reference>.py`; its
traffic mix is `bench/traffic/<traffic>.json`; each metric is read by
`bench/metrics/<metric>.py`, which defines `read(ctx)` and returns the
number or None. So a cell, a mix, a metric or an architecture is added
with files and entries, and no edit here.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import time

import numpy as np

from benchlib import traffic

SAMPLE = 8          # requests compared with the reference in each run


class CellError(RuntimeError):
    pass


# ------------------------------------------------------------------ spec
def load_spec(root: str, workload: str) -> dict:
    """Everything a run of `workload` needs, read from files under `root`."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[cell["config"]]
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load_traffic(root, cell["traffic"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"root": root, "cell": cell, "config": cfg, "traffic": mix,
            "arch": load_module(root, "arch", cfg["model_type"]),
            "end_to_end": e2e, "per_layer": layer}


def load_module(root: str, kind: str, name: str):
    """The module `bench/<kind>/<name>.py` (names may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {path}")
    mod_name = f"bench_{kind}_" + "".join(c if c.isalnum() else "_"
                                          for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- device
def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes():
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCount:
    """Counts XLA compiles and programs loaded from the persistent cache
    from construction to `close`: warm-up work that set-up missed."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.n = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **kw):
        if event == self.COMPILE:
            self.n += 1

    def _on_event(self, event, **kw):
        if event == self.LOAD:
            self.loaded += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_time)
        jax.monitoring.unregister_event_listener(self._on_event)


def _memory() -> str:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return ", ".join(f"{k} {st[k]}" for k in ("bytes_in_use", "bytes_limit",
                                              "peak_bytes_in_use") if k in st)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program written to it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ run
def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, log=print, control: bool = False) -> dict:
    """One run; returns the result object (without printing it).

    With `control`, the fp8 control is put in the program's place for the
    comparison: `checks` and `correct` are then the control's, decided as
    the program's would be, and the program's own checks on the same
    sample are kept under `program_checks` (for `bench/control.py`; the
    benchmark's own runs never do this)."""
    import jax

    from benchlib import serve, trace_reduce
    from benchlib.peaks import peaks_for

    cfg, mix, cell, arch = (spec["config"], spec["traffic"], spec["cell"],
                            spec["arch"])
    slots, rows = traffic.arena(mix, lambda n: arch.slot_bytes(cfg, n))
    dev = device_record()
    peaks = peaks_for(dev["kind"]) if dev["platform"] == "tpu" else None
    spans = serve.Spans(trace)

    with spans("bench.make_weights"):
        params = arch.make_params(cfg, seed)
        jax.block_until_ready(params)
    with spans("bench.build_engine"):
        eng = serve.build_engine(arch, cfg, params, slots, rows)
    with spans("bench.warm"):
        serve.warm(eng, mix, cfg["vocab_size"])
    spans.wrap_engine(eng)
    base = dict(eng.stats)
    setup_s = time.time() - t_start
    log(f"[bench] {cell['name']}: arena {slots} slots x {rows} rows, "
        f"set-up {setup_s:.3f} s, device memory {_memory()}")

    tracer = trace_reduce.Tracer(spec["root"]) if trace else None
    trace_s = min(float(seconds), 5.0)
    traced = {}

    def delta(now):
        return {k: (v - base.get(k, 0)) for k, v in now.items()}

    def stop_tracer():
        tracer.stop()
        traced.update(delta(eng.stats))

    def maybe_stop(elapsed):
        if tracer is not None and tracer.running and elapsed >= trace_s:
            stop_tracer()

    compiles = CompileCount()
    if tracer is not None:
        tracer.start()
    out = serve.closed_loop(eng, mix, seed, seconds, cfg["vocab_size"],
                            spans, on_drive=maybe_stop)
    if tracer is not None and tracer.running:
        stop_tracer()
    stats = delta(out["stats"])
    mem_peak = peak_bytes()
    compiles.close()

    reqs = [{"prompt": rec["prompt"], "max_new": rec["max_new"],
             "tokens": rec["tokens"], "finished": rec["finished"]}
            for rec in out["requests"]]
    window_s = float(out["window_s"])
    for t, n in out["marks"]:
        log(f"[bench] {n} tokens in the window's first {t:.3f} s")
    del eng, out
    gc.collect()

    done = [r for r in reqs if r["finished"]]
    failed = sum(1 for r in done if len(r["tokens"]) != r["max_new"])
    ref = load_module(spec["root"], "reference", cfg["reference"])
    with spans("bench.reference"):
        gaps = compare(ref, params, cfg, done, rows, seed, log, control)
    limit = float(cfg["checks"]["logit_gap"])

    def checks_of(gap):
        return {"logit_gap": {"value": gap, "limit": limit},
                "wrong_length": {"value": float(failed), "limit": 0.0}}

    checks = checks_of(gaps["control" if control else "program"])
    correct = verdict(checks)

    ctx = {"stats": stats, "requests": reqs, "window_s": window_s,
           "setup_s": setup_s, "arch": arch, "config": cfg,
           "widths": arch.served_widths(cfg), "slots": slots,
           "peaks": peaks, "trace": None}
    device = dict(dev, memory_peak_bytes=mem_peak)
    result = {"correct": correct, "attempted": len(done), "failed": failed}
    if trace:
        red = tracer.reduce()
        ctx.update(trace=red, parts=red["parts"], trace_stats=traced)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = read_metrics(spec, spec["per_layer"], ctx)
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = read_metrics(spec, spec["end_to_end"], ctx,
                                         required=True)
        result["device"] = device
    log(f"[bench] window {window_s:.3f} s, {len(reqs)} requests submitted, "
        f"{len(done)} finished, {failed} failed; {compiles.n} programs "
        f"compiled and {compiles.loaded} loaded from the cache in the "
        f"window")
    if control:
        result["program_checks"] = checks_of(gaps["program"])
    result["checks"] = checks
    return result


def verdict(checks: dict) -> bool:
    """`correct`: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def read_metrics(spec: dict, entries: list, ctx: dict,
                 required: bool = False) -> dict:
    out = {}
    for m in entries:
        val = load_module(spec["root"], "metrics", m["name"]).read(ctx)
        if val is None:
            if required:
                raise CellError(f"metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def compare(ref, params, cfg: dict, reqs: list, rows: int, seed: int,
            log=print, control: bool = False) -> dict:
    """The reference over a sample of the requests finished in the
    window, drawn from the seed, the longest always among them: the
    widest gap by which a served token's logit lies below the
    reference's best (`program`).
    With `control`, also the widest gap of the token the fp8 control
    puts first at each of those positions (`control`). Nothing finished
    reads as an infinite gap."""
    if not reqs:
        return {"program": float("inf"), "control": float("inf")}
    longest = max(range(len(reqs)), key=lambda i: reqs[i]["prompt"].size
                  + len(reqs[i]["tokens"]))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = traffic.rng_for(seed, 3)
    k = min(SAMPLE - 1, len(rest))
    pick = [longest] + [rest[j] for j in rng.choice(len(rest), k,
                                                    replace=False)]
    sample = [{"prompt": reqs[i]["prompt"], "tokens": reqs[i]["tokens"]}
              for i in pick]
    t0 = time.time()
    batch = _ref_batch(rows)

    def widest(**kw):
        gaps = ref.served_gaps(params, cfg, sample, rows, batch=batch, **kw)
        return float(max(float(np.max(g)) for g in gaps))

    out = {"program": widest()}
    n_tok = int(sum(len(s["tokens"]) for s in sample))
    log(f"[bench] reference: {len(sample)} requests, {n_tok} served tokens "
        f"compared in {time.time() - t0:.3f} s")
    if control:
        out["control"] = widest(control=True)
    return out


def _ref_batch(rows: int) -> int:
    """Sequences per reference pass: attention scores of 4 x 1536^2."""
    return max(1, (4 * 1536 * 1536) // (rows * rows))
