#!/usr/bin/env python3
"""Bring-up smoke: serve and train internlm2-1.8b at full width on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # tensor-parallel path, four chips

One chip, one process, four phases through the normal entry points
(`build_engine` / `Engine.submit` / `warmup` / `run`, and
`make_sharded_geta_train_step`), weights random from `--seed`:

  serve-dense    24 layers, bf16, contiguous arena, flash decode
  serve-packed   the deployment artifact: pruned at sparsity 0.5, 4-bit
                 packed codes
  serve-paged    paged arena with int8 KV pages
  train          GETA (QASSO) steps at full width, depth cut to fit

Every serving phase is checked against the `xla-ref` oracle on the same
chip: prefill and first-decode logits within `LOGIT_TOL`, and the count
of greedy tokens that match. Training must give finite losses, and its
step-0 loss must match the oracle's within `LOSS_TOL`. Each phase prints
the device, the backend every routed kernel op resolved to (anything but
the chip's kernels fails the run), compile seconds and persistent-cache
hits, the work done, and the device's peak bytes in use.

`--four-chips` runs only the tensor-parallel engine at tp=4 against a
one-device engine on chip 0 in the same process: identical greedy tokens,
shards spread over four chips, per-device bytes below the one-device
engine's. The sharded engine runs XLA's partitionable ops by design (a
Pallas call has no GSPMD partitioning rule), so its reference runs the
same backend.

The last line of standard output is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}, and only when every
phase passed. Without a TPU, or outside a checkout of the repository, the
script names what it found and exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "internlm2-1.8b"
LOGIT_TOL = 5e-2   # max |kernel - oracle| / max |oracle| over the vocab
LOSS_TOL = 1e-2    # |kernel - oracle| / |oracle| of the step-0 loss
TRAIN_LAYERS = 2   # of 24: see `train_reckoning`
HBM_BYTES = 16 * 2**30


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Workload of one run: full width on the chip, the smoke config for
    the CPU rehearsal in the test suite."""
    smoke: bool
    prompt_lens: tuple = (32, 128, 256, 500)
    gen: int = 16
    max_seq: int = 640
    page_size: int = 16
    train_batch: int = 4
    train_seq: int = 256
    train_steps: int = 4


SMOKE_SIZES = Sizes(smoke=True, prompt_lens=(5, 8, 12, 20), gen=6,
                    max_seq=32, page_size=8, train_batch=2, train_seq=16,
                    train_steps=2)


class PhaseError(RuntimeError):
    pass


def _import_repro():
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it from "
                 f"a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.3f} GiB"


def _backends(launches) -> dict:
    """{op: {backend: count}} over recorded kernel dispatches."""
    out: dict = {}
    for ln in launches:
        op = "gemm|" + ln.ops if getattr(ln, "kind", "") == "gemm" \
            else ln.kind
        per = out.setdefault(op, {})
        per[ln.backend] = per.get(ln.backend, 0) + 1
    return out


def _check_backends(label, launches, expect, need_attn=True):
    seen = _backends(launches)
    if not seen:
        raise PhaseError(f"{label}: no routed kernel op was traced")
    bad = {op: b for op, b in seen.items() if set(b) != {expect}}
    if bad:
        raise PhaseError(f"{label}: routed ops off {expect}: {bad}")
    if need_attn and not any("decode_attn" in op for op in seen):
        raise PhaseError(f"{label}: flash decode never dispatched")
    return seen


def _compare(got, want) -> dict:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return {"rel": math.inf, "top1": 0, "rows": int(got.shape[0])}
    rel = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                 1e-30))
    top1 = int(np.sum(got.argmax(-1) == want.argmax(-1)))
    return {"rel": rel, "top1": top1, "rows": int(got.shape[0])}


def _agreement(got, want) -> tuple[int, int, list[int]]:
    """Greedy tokens of `got` equal to `want`'s: (matching, total, the
    matching prefix length of each request)."""
    total = sum(len(t) for t in want.values())
    match = sum(int((got[r][:len(want[r])] == want[r]).sum()) for r in want)
    prefix = [int(next((i for i, (a, b) in enumerate(zip(got[r], want[r]))
                        if a != b), len(want[r]))) for r in sorted(want)]
    return match, total, prefix


# ------------------------------------------------------------- logit probe
def make_probe(lm, max_seq: int, paged=None):
    """jit: (params, qparams, tokens) -> (last prefill logits, first
    decode logits) for one prompt, through `LM.prefill` and
    `LM.decode_step` — the contiguous arena, or the prefill rows written
    into pages when `paged` = (page_size, kv_bits)."""
    import jax
    import jax.numpy as jnp

    from repro.core.quant import kv_quant_encode
    from repro.models.layers import PagedView

    dt = jnp.bfloat16 if lm.cfg.dtype == "bfloat16" else jnp.float32

    def probe(params, qparams, tokens):
        S = tokens.shape[1]
        cache = lm.init_cache(1, max_seq, dtype=dt)
        logits, cache = lm.prefill(params, qparams, cache, tokens,
                                   last_logit_only=True)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        pos = jnp.full((1,), S, jnp.int32)
        pages = None
        if paged is not None:
            P, kv_bits = paged
            lp = -(-max_seq // P)
            pool = lm.init_paged_cache(1, lp + 1, P, dtype=dt,
                                       kv_bits=kv_bits)
            for key, rows in cache.items():        # (nb, 1, S, KVh * dh)
                r = rows[:, 0].reshape(rows.shape[:1] + (max_seq,)
                                       + pool[key].shape[3:4] + (-1,))
                r = jnp.pad(r, ((0, 0), (0, lp * P - max_seq),
                                (0, 0), (0, 0)))
                r = r.reshape((r.shape[0], lp, P) + r.shape[2:])
                if kv_bits is None:
                    pool[key] = pool[key].at[:, 1:].set(r)
                else:
                    codes, scale = kv_quant_encode(r, kv_bits)
                    pool[key] = pool[key].at[:, 1:].set(codes)
                    pool[key + "_scale"] = \
                        pool[key + "_scale"].at[:, 1:].set(scale)
            cache = pool
            pages = PagedView(table=jnp.arange(1, lp + 1)[None],
                              page_size=P, seq_len=max_seq, kv_bits=kv_bits)
        dlogits, _ = lm.decode_step(params, qparams, cache, nxt[:, None],
                                    pos, pages=pages)
        return logits[:, -1], dlogits[:, -1]

    return jax.jit(probe)


# -------------------------------------------------------------- serving
def serve_phase(label, sizes, seed, expect, kernel_backend, **build_kw):
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.kernels import introspect, use_backend
    from repro.launch import compile_cache
    from repro.launch.engine import Engine, build_engine, synthetic_prompts

    scope = (use_backend(kernel_backend) if kernel_backend
             else contextlib.nullcontext())
    c0 = compile_cache.counts()
    with scope, introspect.record_launches() as launches:
        t0 = time.time()
        eng, lm = build_engine(ARCH, smoke=sizes.smoke, max_slots=4,
                               max_seq=sizes.max_seq, seed=seed,
                               page_size=sizes.page_size, **build_kw)
        build_s = time.time() - t0
        prompts = synthetic_prompts(lm.cfg, list(sizes.prompt_lens), seed)
        for p in prompts:
            eng.submit(p, sizes.gen)
        t0 = time.time()
        eng.warmup()
        warm_s = time.time() - t0
        t0 = time.time()
        got = eng.run()
        run_s = time.time() - t0
        paged = ((eng.page_size, eng.kv_bits) if eng.paged else None)
        probe = make_probe(lm, sizes.max_seq, paged)
        probe_tok = jnp.asarray(prompts[-1])[None]
        k_logits = jax.device_get(probe(eng.params, eng.qparams, probe_tok))
    c1 = compile_cache.counts()
    seen = _check_backends(label, launches, expect)

    with use_backend("xla-ref"), introspect.record_launches() as olaunch:
        oracle = Engine(lm, eng.params, eng.qparams, max_slots=4,
                        max_seq=sizes.max_seq, paged=eng.paged,
                        page_size=eng.page_size, kv_bits=eng.kv_bits)
        for p in prompts:
            oracle.submit(p, sizes.gen)
        want = oracle.run()
        probe_o = make_probe(lm, sizes.max_seq, paged)
        o_logits = jax.device_get(probe_o(eng.params, eng.qparams,
                                          probe_tok))
    _check_backends(label + " oracle", olaunch, "xla-ref")

    pre = _compare(k_logits[0], o_logits[0])
    dec = _compare(k_logits[1], o_logits[1])
    match, total, prefix = _agreement(got, want)
    st = eng.stats
    print(f"[{label}] {json.dumps(device_record())} | routed ops: "
          f"{json.dumps(seen, sort_keys=True)}")
    print(f"[{label}] build {build_s:.1f} s, compile (warmup) {warm_s:.1f} s,"
          f" drain {run_s:.2f} s; persistent cache hits "
          f"{c1['hits'] - c0['hits']} of {c1['requests'] - c0['requests']} "
          f"lookups")
    print(f"[{label}] {len(prompts)} requests "
          f"{list(sizes.prompt_lens)} x {sizes.gen} new: prefill "
          f"{st['prefill_tokens']} tokens, decode {st['decode_tokens']} "
          f"tokens in {st['decode_steps']} steps; params "
          f"{_gib(eng.param_bytes())}, kv {_gib(eng.kv_bytes())}; peak "
          f"{_gib(_peak_bytes())}")
    print(f"[{label}] vs xla-ref oracle: prefill logits rel err "
          f"{pre['rel']:.3e} (top-1 {pre['top1']}/{pre['rows']}), "
          f"first-decode rel err {dec['rel']:.3e} (top-1 "
          f"{dec['top1']}/{dec['rows']}), tol {LOGIT_TOL:g}; greedy tokens "
          f"matching {match}/{total}, matching prefix per request {prefix}")
    if sorted(got) != sorted(want) or any(
            len(got[r]) != sizes.gen for r in got):
        raise PhaseError(f"{label}: requests did not all finish: "
                         f"{ {r: len(t) for r, t in got.items()} }")
    if not (pre["rel"] <= LOGIT_TOL and dec["rel"] <= LOGIT_TOL):
        raise PhaseError(f"{label}: logits off the oracle past {LOGIT_TOL}")
    del eng, oracle
    gc.collect()


# -------------------------------------------------------------- training
def train_reckoning(cfg, n_layers: int) -> dict:
    """Bytes the GETA step keeps live at `n_layers`: params and their
    gradients in the config dtype, f32 Adam moments, with params and
    moments held twice across the step (its inputs are not donated)."""
    import dataclasses as dc
    n = dc.replace(cfg, n_layers=n_layers).param_count()
    item = 2 if cfg.dtype == "bfloat16" else 4
    per = 2 * (item + 8) + item
    return {"layers": n_layers, "params": n, "bytes": n * per}


def train_phase(sizes, seed, expect, kernel_backend):
    import contextlib

    import jax
    import numpy as np

    from repro.configs import CompressionConfig, get_arch
    from repro.data.synthetic import batch_for
    from repro.kernels import introspect, use_backend
    from repro.launch import compile_cache
    from repro.launch.mesh import make_subset_mesh
    from repro.launch.train import build_geta, make_sharded_geta_train_step
    from repro.models.transformer import LM

    cfg = get_arch(ARCH, smoke=sizes.smoke)
    label = "train"
    if not sizes.smoke:
        full = train_reckoning(cfg, cfg.n_layers)
        cut = train_reckoning(cfg, TRAIN_LAYERS)
        print(f"[{label}] depth cut {cfg.n_layers} -> {TRAIN_LAYERS} layers "
              f"at full width: {full['params'] / 1e9:.3f} B params need "
              f"~{_gib(full['bytes'])} live (params + grads + f32 Adam "
              f"moments, inputs and outputs of the step) > {_gib(HBM_BYTES)}"
              f"; at {TRAIN_LAYERS} layers {cut['params'] / 1e9:.3f} B "
              f"params need ~{_gib(cut['bytes'])}")
        cfg = dataclasses.replace(cfg, n_layers=TRAIN_LAYERS)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(seed))
    qparams = lm.init_qparams(params, bits_init=16.0)
    comp = CompressionConfig(warmup_steps=2, projection_periods=2,
                             projection_steps=2, pruning_periods=3,
                             pruning_steps=2, cooldown_steps=2)
    _, qasso = build_geta(lm, comp, lr=3e-4)
    mesh = make_subset_mesh(1)
    batches = [batch_for(cfg, seed, i, sizes.train_batch, sizes.train_seq)
               for i in range(sizes.train_steps)]

    with use_backend("xla-ref"), introspect.record_launches() as olaunch:
        ref_loss = float(jax.jit(lm.loss)(params, qparams, batches[0]))
    _check_backends(label + " oracle", olaunch, "xla-ref", need_attn=False)

    scope = (use_backend(kernel_backend) if kernel_backend
             else contextlib.nullcontext())
    c0 = compile_cache.counts()
    with scope, introspect.record_launches() as launches:
        jstep, (p_sh, q_sh, s_sh, b_sh) = make_sharded_geta_train_step(
            lm, qasso, mesh, params, qparams)
        params = jax.device_put(params, p_sh)
        qparams = jax.device_put(qparams, q_sh)
        qstate = jax.device_put(qasso.init(params, qparams), s_sh)
        b0 = jax.device_put(batches[0], b_sh)
        t0 = time.time()
        compiled = jstep.lower(params, qparams, qstate, b0).compile()
        compile_s = time.time() - t0
    c1 = compile_cache.counts()
    seen = _check_backends(label, launches, expect, need_attn=False)
    mem = compiled.memory_analysis()

    losses, step_s = [], []
    for b in batches:
        t0 = time.time()
        params, qparams, qstate, metrics = compiled(
            params, qparams, qstate, jax.device_put(b, b_sh))
        losses.append(float(metrics["loss"]))
        step_s.append(time.time() - t0)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[{label}] {json.dumps(device_record())} | routed ops: "
          f"{json.dumps(seen, sort_keys=True)}")
    print(f"[{label}] {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{n_params / 1e9:.3f} B params; compile {compile_s:.1f} s, "
          f"persistent cache hits {c1['hits'] - c0['hits']} of "
          f"{c1['requests'] - c0['requests']} lookups; compiled step "
          f"arguments {_gib(mem.argument_size_in_bytes)}, outputs "
          f"{_gib(mem.output_size_in_bytes)}, temporaries "
          f"{_gib(mem.temp_size_in_bytes)}")
    print(f"[{label}] {len(losses)} steps of {sizes.train_batch}x"
          f"{sizes.train_seq} tokens in {sum(step_s):.2f} s, losses "
          f"{[round(x, 4) for x in losses]}; step-0 oracle loss "
          f"{ref_loss:.4f} (tol {LOSS_TOL:g}); peak {_gib(_peak_bytes())}")
    if not all(math.isfinite(x) for x in losses):
        raise PhaseError(f"{label}: non-finite loss {losses}")
    if abs(losses[0] - ref_loss) > LOSS_TOL * abs(ref_loss):
        raise PhaseError(f"{label}: step-0 loss {losses[0]} vs oracle "
                         f"{ref_loss}")


# ------------------------------------------------------- tensor parallel
def four_chip_phase(sizes, seed):
    import jax
    import numpy as np

    from repro.kernels import introspect, use_backend
    from repro.launch.engine import build_engine, synthetic_prompts

    label = "serve-tp4"
    if len(jax.devices()) < 4:
        raise PhaseError(f"{label}: needs 4 devices, found "
                         f"{len(jax.devices())}")
    with introspect.record_launches() as launches:
        eng4, lm = build_engine(ARCH, smoke=sizes.smoke, max_slots=4,
                                max_seq=sizes.max_seq, seed=seed, tp=4)
        prompts = synthetic_prompts(lm.cfg, list(sizes.prompt_lens), seed)
        for p in prompts:
            eng4.submit(p, sizes.gen)
        t0 = time.time()
        got = eng4.run()
        run4 = time.time() - t0
    seen = _check_backends(label, launches, "xla-ref")
    # KV heads shard only when 4 divides them (8 at full width); the
    # smoke config's 2 replicate, the engine's documented fallback
    kv_split = lm.cfg.n_kv_heads % 4 == 0
    spread = {}
    for name, leaf, split in (
            ("param blocks.0.attn.wq", eng4.params["blocks.0.attn.wq"], True),
            ("kv blocks.0.k", eng4.caches["blocks.0.k"], kv_split)):
        shards = leaf.addressable_shards
        devs = sorted({s.device.id for s in shards})
        spread[name] = {"devices": devs,
                        "shard": list(shards[0].data.shape),
                        "global": list(leaf.shape)}
        if len(devs) != 4 or (split and shards[0].data.size * 4 != leaf.size):
            raise PhaseError(f"{label}: {name} is not split over 4 chips: "
                             f"{spread[name]}")
    p4, k4 = eng4.param_bytes(per_device=True), eng4.kv_bytes(per_device=True)
    del eng4
    gc.collect()

    with use_backend("xla-ref"), introspect.record_launches() as olaunch:
        eng1, _ = build_engine(ARCH, smoke=sizes.smoke, max_slots=4,
                               max_seq=sizes.max_seq, seed=seed)
        for p in prompts:
            eng1.submit(p, sizes.gen)
        t0 = time.time()
        want = eng1.run()
        run1 = time.time() - t0
    _check_backends(label + " reference", olaunch, "xla-ref")
    dev1 = sorted({s.device.id for s in
                   eng1.params["blocks.0.attn.wq"].addressable_shards})
    p1, k1 = eng1.param_bytes(), eng1.kv_bytes()
    same = all(np.array_equal(got[r], want[r]) for r in want) \
        and sorted(got) == sorted(want)
    match, total, prefix = _agreement(got, want)
    print(f"[{label}] {json.dumps(device_record())} | routed ops "
          f"(GSPMD, both engines): {json.dumps(seen, sort_keys=True)}")
    print(f"[{label}] shards: {json.dumps(spread)}; tp=1 engine on devices "
          f"{dev1}")
    print(f"[{label}] per-device bytes tp=4 vs tp=1: params {p4} vs {p1}, "
          f"kv {k4} vs {k1}; drain {run4:.2f} s vs {run1:.2f} s; peak "
          f"(chip 0) {_gib(_peak_bytes())}")
    print(f"[{label}] greedy tokens tp=4 == tp=1: {same} over {total} "
          f"tokens ({match} matching, matching prefix per request "
          f"{prefix})")
    if not same:
        raise PhaseError(f"{label}: tp=4 tokens differ from tp=1")
    if not (p4 < p1 and (k4 < k1 or not kv_split)):
        raise PhaseError(f"{label}: per-device bytes did not shrink")


# --------------------------------------------------------------------- run
def run(sizes: Sizes, *, seed: int = 0, four_chips: bool = False,
        kernel_backend: str | None = None,
        expect: str = "pallas-tpu") -> list[str]:
    """Run the phases; returns the names of those that failed.

    `kernel_backend` forces the backend of the kernel-side runs (the CPU
    rehearsal passes "pallas-interpret"); None leaves dispatch to pick,
    and `expect` is what every routed op must then have resolved to."""
    import traceback

    from repro.models.layers import use_decode_attn

    if four_chips:
        phases = [("serve-tp4", lambda: four_chip_phase(sizes, seed))]
    else:
        phases = [
            ("serve-dense", lambda: serve_phase(
                "serve-dense", sizes, seed, expect, kernel_backend)),
            ("serve-packed", lambda: serve_phase(
                "serve-packed", sizes, seed, expect, kernel_backend,
                pruned=True, sparsity=0.5, packed=True, bits_init=4.0)),
            ("serve-paged", lambda: serve_phase(
                "serve-paged", sizes, seed, expect, kernel_backend,
                paged=True, kv_bits=8)),
            ("train", lambda: train_phase(sizes, seed, expect,
                                          kernel_backend)),
        ]
    failed = []
    with use_decode_attn(True):
        for name, fn in phases:
            t0 = time.time()
            try:
                fn()
                print(f"[{name}] passed in {time.time() - t0:.1f} s",
                      flush=True)
            except Exception:
                traceback.print_exc()
                print(f"[{name}] FAILED after {time.time() - t0:.1f} s",
                      flush=True)
                failed.append(name)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 vs tp=1 engine comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _import_repro()

    import jax

    from repro.launch import compile_cache

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU: JAX found {dev['count']} "
              f"{dev['platform']} device(s) ({dev['kind']})",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} TPU chips, found {dev['count']}",
              file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    print(f"chip_smoke: {json.dumps(dev)}; jax {jax.__version__}; "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.time()
    failed = run(Sizes(smoke=False), seed=args.seed,
                 four_chips=args.four_chips)
    c = compile_cache.counts()
    print(f"chip_smoke: {time.time() - t0:.1f} s; persistent cache hits "
          f"{c['hits']} of {c['requests']} lookups", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
