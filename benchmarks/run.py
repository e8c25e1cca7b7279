"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. `us_per_call` is the wall
time of one GETA train step on this host (CPU); `derived` carries the
table's headline quantity (accuracy/EM @ rel-BOPs, ablation deltas, ...).

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_table2_resnet20(fast=False):
    """Table 2: ResNet20/CIFAR10 — GETA structured vs baseline (wt quant)."""
    from benchmarks.geta_experiments import (RESNET20_R, run_baseline_cnn,
                                             run_geta_cnn)
    steps = 120 if fast else 240
    base = run_baseline_cnn(RESNET20_R, steps=steps)
    geta = run_geta_cnn(RESNET20_R, steps=steps, sparsity=0.35,
                        act_quant=False)
    us = geta["wall_s"] / max(steps, 1) * 1e6
    _row("table2_resnet20_baseline", 0.0,
         f"acc={base['acc']:.3f};rel_bops=1.0")
    _row("table2_resnet20_geta", us,
         f"acc={geta['acc']:.3f};rel_bops={geta['rel_bops']:.4f};"
         f"sparsity={geta['sparsity']:.2f};bits={geta['mean_bits']:.1f}")
    return {"base": base, "geta": geta}


def bench_table4_vgg7(fast=False):
    """Table 4: VGG7/CIFAR10 — weight AND activation quantization."""
    from benchmarks.geta_experiments import (VGG7_R, run_baseline_cnn,
                                             run_geta_cnn)
    steps = 100 if fast else 200
    base = run_baseline_cnn(VGG7_R, steps=steps)
    geta = run_geta_cnn(VGG7_R, steps=steps, sparsity=0.5, act_quant=True)
    us = geta["wall_s"] / max(steps, 1) * 1e6
    _row("table4_vgg7_baseline", 0.0, f"acc={base['acc']:.3f};rel_bops=1.0")
    _row("table4_vgg7_geta_wa", us,
         f"acc={geta['acc']:.3f};rel_bops={geta['rel_bops']:.4f}")
    return {"base": base, "geta": geta}


def bench_table5_resnet56(fast=False):
    """Table 5 analogue: deeper CNN at two sparsities (40%/50%)."""
    from benchmarks.geta_experiments import RESNET56_R, run_geta_cnn
    steps = 100 if fast else 200
    out = {}
    for sp in (0.4, 0.5):
        r = run_geta_cnn(RESNET56_R, steps=steps, sparsity=sp)
        us = r["wall_s"] / max(steps, 1) * 1e6
        _row(f"table5_resnet56_sp{int(sp*100)}", us,
             f"acc={r['acc']:.3f};rel_bops={r['rel_bops']:.4f}")
        out[sp] = r
    return out


def bench_table3_bert(fast=False):
    """Table 3: BERT/SQuAD-style — GETA joint vs prune-then-PTQ."""
    from benchmarks.geta_experiments import (run_geta_bert,
                                             run_prune_then_ptq_bert)
    steps = 100 if fast else 200
    sparsities = (0.3, 0.5) if fast else (0.1, 0.3, 0.5, 0.7)
    out = {}
    for sp in sparsities:
        t0 = time.time()
        joint = run_geta_bert(sp, steps=steps)
        us = (time.time() - t0) / steps * 1e6
        seq = run_prune_then_ptq_bert(sp, steps=steps)
        _row(f"table3_bert_sp{int(sp*100)}_geta", us,
             f"em={joint['em']:.3f};rel_bops={joint['rel_bops']:.4f}")
        _row(f"table3_bert_sp{int(sp*100)}_prune_ptq", us,
             f"em={seq['em']:.3f};rel_bops={seq['rel_bops']:.4f}")
        out[sp] = {"joint": joint, "sequential": seq}
    return out


def bench_fig4a_ablation(fast=False):
    """Fig 4a: remove each QASSO stage, measure the accuracy drop."""
    from benchmarks.geta_experiments import RESNET56_R, run_geta_cnn
    steps = 80 if fast else 160
    full = run_geta_cnn(RESNET56_R, steps=steps, sparsity=0.35)
    _row("fig4a_full", 0.0, f"acc={full['acc']:.3f}")
    out = {"full": full}
    for stage in ("warmup", "projection", "joint", "cooldown"):
        r = run_geta_cnn(RESNET56_R, steps=steps, sparsity=0.35,
                         skip_stage=stage)
        _row(f"fig4a_no_{stage}", 0.0,
             f"acc={r['acc']:.3f};delta={r['acc']-full['acc']:+.3f}")
        out[stage] = r
    return out


def bench_fig4b_frontier(fast=False):
    """Fig 4b: sparsity x bit-range compression frontier."""
    from benchmarks.geta_experiments import RESNET56_R, run_geta_cnn
    steps = 60 if fast else 120
    grid_sp = (0.3, 0.6) if fast else (0.3, 0.5, 0.7)
    grid_b = ((4, 6),) if fast else ((2, 4), (4, 6), (6, 8))
    out = {}
    for sp in grid_sp:
        for (bl, bu) in grid_b:
            r = run_geta_cnn(RESNET56_R, steps=steps, sparsity=sp,
                             b_l=float(bl), b_u=float(bu) + 8)
            _row(f"fig4b_sp{int(sp*100)}_b{bl}", 0.0,
                 f"acc={r['acc']:.3f};rel_bops={r['rel_bops']:.4f}")
            out[(sp, bl)] = r
    return out


def bench_kernel_fake_quant(fast=False):
    """Fused fake-quant op vs eager op-chain (CPU timings; the TPU win is
    the single HBM round-trip, see DESIGN.md)."""
    from repro.core.quant import fake_quant
    from repro.kernels.ref import fake_quant_fwd_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024))
    d, qm, t = jnp.float32(0.05), jnp.float32(1.2), jnp.float32(0.9)

    fused = jax.jit(lambda x: fake_quant(x, d, qm, t))
    ref = jax.jit(lambda x: fake_quant_fwd_ref(x, d, qm, t))
    fused(x).block_until_ready()
    ref(x).block_until_ready()
    n = 20 if fast else 50
    t0 = time.time()
    for _ in range(n):
        fused(x).block_until_ready()
    tf = (time.time() - t0) / n * 1e6
    t0 = time.time()
    for _ in range(n):
        ref(x).block_until_ready()
    tr = (time.time() - t0) / n * 1e6
    _row("kernel_fake_quant_fused", tf, f"ref_us={tr:.1f}")
    return {"fused_us": tf, "ref_us": tr}


def bench_kernel_fused_joint(fast=False):
    """Fused x @ (fake_quant(w) * mask) GEMM epilogue vs the unfused
    quantize -> mask -> matmul chain (three HBM passes of W). Timed on this
    host's default dispatch backend; the TPU win is the single HBM pass of
    W (DESIGN.md §4)."""
    from repro.core.quant import fake_quant
    from repro.kernels import ops
    m, k, n = (256, 1024, 1024) if fast else (512, 2048, 2048)
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (n,)) > 0.4).astype(
        jnp.float32)
    d, qm, t = jnp.float32(0.05), jnp.float32(1.2), jnp.float32(0.9)

    fused = jax.jit(lambda x, w: ops.fq_masked_matmul_op(x, w, mask, d, qm, t))
    unfused = jax.jit(
        lambda x, w: x @ (fake_quant(w, d, qm, t) * mask[None, :]))
    fused(x, w).block_until_ready()
    unfused(x, w).block_until_ready()
    reps = 10 if fast else 30
    t0 = time.time()
    for _ in range(reps):
        fused(x, w).block_until_ready()
    tf = (time.time() - t0) / reps * 1e6
    t0 = time.time()
    for _ in range(reps):
        unfused(x, w).block_until_ready()
    tu = (time.time() - t0) / reps * 1e6
    _row("kernel_fused_joint_gemm", tf,
         f"unfused_us={tu:.1f};speedup={tu/max(tf,1e-9):.2f}x")
    return {"fused_us": tf, "unfused_us": tu}


def bench_serve_decode(fast=False):
    """Decode throughput: dense fake-quant params vs compressed Subnet int
    codes (the quant-dequant GEMM epilogue), same smoke model. Timing is
    decode-only (the prefill inside serve_loop warms the jit, so compile
    and init are excluded)."""
    from repro.launch.serve import serve_loop
    gen = 8 if fast else 16
    out = {}
    for mode, compressed in (("dense", False), ("compressed", True)):
        stats = {}
        serve_loop("internlm2-1.8b", smoke=True, batch=2, prompt_len=4,
                   gen=gen, compressed=compressed, verbose=False,
                   stats=stats)
        us = stats["decode_s"] * 1e6 / max(stats["tokens"], 1)
        _row(f"serve_decode_{mode}", us,
             f"tok_per_s={stats['tok_per_s']:.1f}")
        out[mode] = us
    _row("serve_decode_compressed_speedup", 0.0,
         f"{out['dense']/max(out['compressed'],1e-9):.2f}x")
    return out


def bench_engine_prefill(fast=False):
    """One-shot parallel prefill (`LM.prefill`, a single (B, S) forward
    that fills the caches) vs the sequential per-token decode-step prefill
    the static serve_loop uses. Same model, same tokens, both jit-warmed;
    the row's derived field carries the speedup."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.subnet import prepare_serving
    from repro.models.transformer import LM

    cfg = get_arch("internlm2-1.8b", smoke=True)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    params, qparams, _ = prepare_serving(lm, params, compressed=True)
    B, S = 2, (16 if fast else 32)
    max_seq = S + 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

    step = jax.jit(lm.decode_step)
    prefill = jax.jit(lm.prefill)

    def sequential():
        caches = lm.init_cache(B, max_seq, dtype=jnp.float32)
        for p in range(S):
            lg, caches = step(params, qparams, caches, toks[:, p:p + 1],
                              jnp.int32(p))
        return lg

    def oneshot():
        caches = lm.init_cache(B, max_seq, dtype=jnp.float32)
        lg, _ = prefill(params, qparams, caches, toks)
        return lg

    jax.block_until_ready(sequential())
    jax.block_until_ready(oneshot())
    reps = 3 if fast else 5
    out = {}
    for name, fn in (("sequential", sequential), ("oneshot", oneshot)):
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn())
        wall = (time.time() - t0) / reps
        out[name] = B * S / max(wall, 1e-9)
        _row(f"engine_prefill_{name}", wall * 1e6 / (B * S),
             f"tok_per_s={out[name]:.1f}")
    _row("engine_prefill_oneshot_speedup", 0.0,
         f"{out['oneshot']/max(out['sequential'],1e-9):.2f}x")
    return out


def bench_engine_continuous(fast=False):
    """Continuous vs static batching at mixed request lengths. Static
    lockstep decodes every group to its longest member (the short request
    burns slots as padding); the engine evicts on completion and admits
    the next queued request into the freed slot. tok/s counts *useful*
    tokens only, decode-time only (prefill/compile excluded for both)."""
    from repro.launch.engine import build_engine, synthetic_prompts
    from repro.launch.serve import serve_loop

    slots = 2
    gens = [6, 18, 6, 18] if fast else [8, 32, 8, 32, 12, 24]
    prompt_len = 6
    # both arms time decode only, and each request's first token comes from
    # the untimed prefill — so useful decoded tokens are (gen-1) per
    # request (exactly what eng.stats['decode_tokens'] counts)
    useful = sum(g - 1 for g in gens)

    # static: consecutive groups of `slots`, each decoded to max(gens)
    static_s = 0.0
    for i in range(0, len(gens), slots):
        grp = gens[i:i + slots]
        stats = {}
        serve_loop("internlm2-1.8b", True, len(grp), prompt_len, max(grp),
                   compressed=True, verbose=False, stats=stats)
        static_s += stats["decode_s"]
    static_tps = useful / max(static_s, 1e-9)
    _row("engine_static_batching", static_s * 1e6 / useful,
         f"tok_per_s={static_tps:.1f}")

    eng, lm = build_engine("internlm2-1.8b", True, compressed=True,
                           max_slots=slots, max_seq=prompt_len + max(gens))
    for p, g in zip(synthetic_prompts(lm.cfg, [prompt_len] * len(gens)),
                    gens):
        eng.submit(p, g)
    eng.warmup()
    eng.run()
    cont_tps = eng.stats["decode_tokens"] / max(eng.stats["decode_s"], 1e-9)
    _row("engine_continuous_batching",
         eng.stats["decode_s"] * 1e6 / max(eng.stats["decode_tokens"], 1),
         f"tok_per_s={cont_tps:.1f};occupancy="
         f"{eng.throughput()['slot_occupancy']:.2f}")
    _row("engine_continuous_speedup", 0.0,
         f"{cont_tps/max(static_tps,1e-9):.2f}x")
    return {"static": static_tps, "continuous": cont_tps}


def bench_engine_decode_pruned(fast=False):
    """Slim serving: engine decode on physically pruned shapes at sparsity
    0 / 0.3 / 0.5 (magnitude masks, compressed int codes on the sliced
    weights). The derived field carries realized param + KV-arena bytes —
    the paper's compression claim in bytes actually allocated, not mask
    zeros — and the s30/s50 rows should sit measurably below the keep-all
    s0 row in us/token (smaller GEMMs, fewer KV rows)."""
    from repro.launch.engine import build_engine, synthetic_prompts

    slots = 4
    gen = 12 if fast else 24
    lens = [6, 6, 6, 6]
    out = {}
    for tag, sp in (("s0", 0.0), ("s30", 0.3), ("s50", 0.5)):
        eng, lm = build_engine("internlm2-1.8b", True, compressed=True,
                               pruned=sp > 0, sparsity=sp, max_slots=slots,
                               max_seq=max(lens) + gen)
        for p in synthetic_prompts(lm.cfg, lens):
            eng.submit(p, gen)
        eng.warmup()
        eng.run()
        us = eng.stats["decode_s"] * 1e6 / max(eng.stats["decode_tokens"], 1)
        realized = eng.serving_meta.get("sparsity", 0.0)
        _row(f"engine_decode_pruned_{tag}", us,
             f"tok_per_s={eng.throughput()['decode_tok_per_s']:.1f};"
             f"sparsity={realized:.2f};"
             f"param_bytes={eng.param_bytes()};kv_bytes={eng.kv_bytes()}")
        out[tag] = {"us": us, "param_bytes": eng.param_bytes(),
                    "kv_bytes": eng.kv_bytes()}
    _row("engine_decode_pruned_s50_speedup", 0.0,
         f"{out['s0']['us']/max(out['s50']['us'],1e-9):.2f}x;"
         f"kv_shrink={out['s0']['kv_bytes']/max(out['s50']['kv_bytes'],1):.2f}x")
    return out


def bench_engine_decode_packed(fast=False):
    """Sub-byte packed serving: engine decode from bit-packed word streams
    at learned widths 8 / 4 / 2 (`--packed`, quantizers initialized at
    each width so the artifact genuinely stores that many bits). The
    derived field carries tokens/s plus the realized served `param_bytes`
    and the packed-vs-int8 container ratio — the ISSUE's ≤0.55x-at-4-bit
    claim as bytes actually allocated (4-bit packs 8 codes per int32 word
    = exactly 0.5x its int8 container; 2-bit 0.25x)."""
    from repro.launch.engine import build_engine, synthetic_prompts

    slots = 4
    gen = 12 if fast else 24
    lens = [6, 6, 6, 6]
    out = {}
    for tag, bits in (("b8", 8.0), ("b4", 4.0), ("b2", 2.0)):
        eng, lm = build_engine("internlm2-1.8b", True, packed=True,
                               bits_init=bits, max_slots=slots,
                               max_seq=max(lens) + gen)
        for p in synthetic_prompts(lm.cfg, lens):
            eng.submit(p, gen)
        eng.warmup()
        eng.run()
        us = eng.stats["decode_s"] * 1e6 / max(eng.stats["decode_tokens"], 1)
        m = eng.serving_meta
        ratio = (m["weight_bytes_compressed"]
                 / max(m["weight_bytes_unpacked"], 1))
        _row(f"engine_decode_packed_{tag}", us,
             f"tok_per_s={eng.throughput()['decode_tok_per_s']:.1f};"
             f"param_bytes={eng.param_bytes()};"
             f"weight_bytes={m['weight_bytes_compressed']};"
             f"vs_int8={ratio:.2f}x")
        out[tag] = {"us": us, "param_bytes": eng.param_bytes(),
                    "ratio": ratio}
    return out


def bench_engine_decode_attn(fast=False):
    """Fused flash-decode attention on the engine decode path: kernel arm
    vs the legacy full-length einsum arm, across dense / pruned(s50) /
    packed(b4) engines, same weights/prompts/seed per config (the arms
    must be token-identical — asserted here, same contract as the
    `--decode-attn-parity` CI smoke). The derived field carries both
    arms' tok/s plus the analytic decode-attention roofline
    (`roofline.analysis.decode_attn_row`): attained-vs-roof HBM bandwidth
    of the arena traffic at the measured step time. Persists everything
    to BENCH_decode.json at the repo root — the tracked decode perf
    trajectory."""
    import json
    import os

    from repro.launch.engine import build_engine, synthetic_prompts
    from repro.models.layers import use_decode_attn
    from repro.roofline.analysis import HBM_BW, decode_attn_row

    slots = 4
    gen = 12 if fast else 24
    lens = [6, 6, 6, 6]
    configs = [
        ("dense", {}),
        ("pruned_s50", dict(compressed=True, pruned=True, sparsity=0.5)),
        ("packed_b4", dict(packed=True, bits_init=4.0)),
    ]
    results = {}
    for tag, kw in configs:
        arms = {}
        tokens = {}
        # several drain cycles per arm, best cycle kept: one cycle is ~a
        # dozen decode steps, far too short for stable wall timing on a
        # shared host, so the minimum-us/token cycle (least scheduler
        # interference) is the recorded figure for both arms alike
        reps = 3 if fast else 10
        for arm, enabled in (("einsum", False), ("kernel", True)):
            with use_decode_attn(enabled):
                eng, lm = build_engine("internlm2-1.8b", True,
                                       max_slots=slots,
                                       max_seq=max(lens) + gen, **kw)
                eng.warmup()
                best = None
                for r in range(reps):
                    s0 = dict(eng.stats)
                    for p in synthetic_prompts(lm.cfg, lens):
                        eng.submit(p, gen)
                    tokens[arm] = eng.run()
                    dsec = eng.stats["decode_s"] - s0["decode_s"]
                    dtok = eng.stats["decode_tokens"] - s0["decode_tokens"]
                    dstep = eng.stats["decode_steps"] - s0["decode_steps"]
                    cyc = {
                        "us_per_tok": dsec * 1e6 / max(dtok, 1),
                        "tok_per_s": dtok / max(dsec, 1e-9),
                        "step_s": dsec / max(dstep, 1),
                    }
                    if best is None or cyc["us_per_tok"] < best["us_per_tok"]:
                        best = cyc
            arms[arm] = best
        for rid in tokens["einsum"]:
            np.testing.assert_array_equal(
                tokens["kernel"][rid], tokens["einsum"][rid],
                err_msg=f"decode-attn arms diverged ({tag}, request {rid})")
        # analytic roofline at this engine's *served* attention shapes
        # (pruned subnets decode fewer kv heads — lm.shapes carries them)
        att = [sh for sh in lm.shapes if sh.n_heads > 0]
        cache_bytes = jnp.dtype(eng._cache_dtype).itemsize
        ctx = max(lens) + gen / 2.0    # mean valid cache length over decode
        roof = decode_attn_row(
            batch=slots, ctx=ctx,
            n_heads=int(np.mean([sh.n_heads for sh in att])),
            n_kv_heads=int(np.mean([sh.n_kv_heads for sh in att])),
            d_head=int(np.mean([sh.d_head for sh in att])),
            n_layers=len(att), cache_bytes=cache_bytes)
        step_s = arms["kernel"]["step_s"]
        roofline = {
            "bytes_per_step": roof.bytes_hbm,
            "flops_per_step": roof.flops,
            "roof_step_s": roof.roof_s,
            "attained_gbps": roof.attained_gbps(step_s),
            "frac_of_roof": roof.frac_of_roof(step_s),
            "hbm_roof_gbps": HBM_BW / 1e9,
        }
        speedup = (arms["einsum"]["us_per_tok"]
                   / max(arms["kernel"]["us_per_tok"], 1e-9))
        _row(f"engine_decode_attn_{tag}", arms["kernel"]["us_per_tok"],
             f"tok_per_s={arms['kernel']['tok_per_s']:.1f};"
             f"einsum_tok_per_s={arms['einsum']['tok_per_s']:.1f};"
             f"speedup={speedup:.2f}x;"
             f"attained_gbps={roofline['attained_gbps']:.2f};"
             f"frac_of_roof={roofline['frac_of_roof']:.4f}")
        results[tag] = {"kernel": arms["kernel"], "einsum": arms["einsum"],
                        "speedup": speedup, "roofline": roofline,
                        "token_identical": True}
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_decode.json")
    payload = {
        "bench": "engine_decode_attn",
        "arch": "internlm2-1.8b(smoke)",
        "workload": {"slots": slots, "prompt_lens": lens, "gen": gen},
        "host_backend": jax.default_backend(),
        "rows": results,
    }
    with open(os.path.abspath(path), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def bench_engine_decode_speculative(fast=False):
    """Self-speculative decoding from the nested GETA subnet family: a
    masked-checkpoint b8 target verified against its own s50-sliced
    packed draft (`launch.speculative.build_checkpoint_engines` — the
    deployment shape a GETA cooldown checkpoint serves), across draft
    windows k in {2, 4, 8}. Headline metric is accepted-tokens/s vs the
    never-drafted b8 baseline on the *same* target arrays, with the
    acceptance rate quoted (the b8 draft is the target at its surviving
    widths, so acceptance ~1 and the draft's ~2x-cheaper sliced steps
    carry the win; the b2-draft row shows the aggressive end where
    acceptance, not step cost, is the binding constraint). Both engines
    must be token-identical per cell — asserted, same oracle as the
    `--speculative --smoke` CI step. Persists to BENCH_speculative.json
    at the repo root.

    Workload note: gen is pinned at 16 — the never-drafted baseline
    decodes through `_window`'s fused on-device scans (one host sync per
    up-to-32-token window), so on this smoke-scale CPU model long
    generations amortize the baseline's sync cost faster than the
    speculative path's one-sync-per-round can match; short generations
    are where the draft's ~2x-cheaper sliced steps show through. Real
    model scales shift the balance toward compute (and speculation) at
    every gen."""
    import json
    import os

    from repro.launch.engine import synthetic_prompts
    from repro.launch.speculative import build_checkpoint_engines

    slots = 4
    gen = 16
    lens = [6, 6, 6, 6]
    reps = 3 if fast else 8
    ks = [2, 4, 8]

    def cycles(eng, lm):
        # several drain cycles, best cycle kept (same rationale as
        # bench_engine_decode_attn: one cycle is too short for stable
        # wall timing, the min-us/token cycle has least interference)
        best, toks = None, None
        for _ in range(reps):
            s0 = dict(eng.stats)
            for p in synthetic_prompts(lm.cfg, lens):
                eng.submit(p, gen)
            toks = eng.run()
            d = {k: eng.stats[k] - s0[k] for k in s0}
            cyc = {
                "us_per_tok": d["decode_s"] * 1e6
                / max(d["decode_tokens"], 1),
                "tok_per_s": d["decode_tokens"] / max(d["decode_s"], 1e-9),
                "acceptance": d["spec_accepted"] / max(d["spec_drafted"], 1),
            }
            if best is None or cyc["us_per_tok"] < best["us_per_tok"]:
                best = cyc
        return best, toks

    spec, base, lm = build_checkpoint_engines(
        "internlm2-1.8b", True, sparsity=0.5, draft_bits=8.0,
        draft_k=max(ks), max_slots=slots, max_seq=max(lens) + gen)
    base.warmup()
    base_best, base_toks = cycles(base, lm)
    _row("engine_decode_speculative_baseline_b8", base_best["us_per_tok"],
         f"tok_per_s={base_best['tok_per_s']:.1f};speculative=off")

    results = {"baseline_b8": base_best}
    spec.warmup()       # compiles every k in {0} + pow2 <= max(ks) once
    for k in ks:
        spec.draft_k = k
        best, toks = cycles(spec, lm)
        for (_, got), (_, want) in zip(sorted(toks.items()),
                                       sorted(base_toks.items())):
            np.testing.assert_array_equal(
                got, want, err_msg=f"speculative k={k} diverged from the "
                f"never-drafted baseline")
        speedup = base_best["us_per_tok"] / max(best["us_per_tok"], 1e-9)
        _row(f"engine_decode_speculative_k{k}", best["us_per_tok"],
             f"accepted_tok_per_s={best['tok_per_s']:.1f};"
             f"baseline_tok_per_s={base_best['tok_per_s']:.1f};"
             f"speedup={speedup:.2f}x;"
             f"acceptance={best['acceptance']:.2f};draft=s50/b8")
        results[f"k{k}"] = {**best, "speedup": speedup,
                            "draft_bits": 8.0, "token_identical": True}

    # the aggressive end of the subnet family: a 2-bit draft is cheaper
    # per step but its proposals rarely survive verification — the row
    # documents that acceptance, not draft cost, binds at low bits
    spec2, _, lm2 = build_checkpoint_engines(
        "internlm2-1.8b", True, sparsity=0.5, draft_bits=2.0, draft_k=4,
        max_slots=slots, max_seq=max(lens) + gen)
    spec2.warmup()
    best2, toks2 = cycles(spec2, lm2)
    for (_, got), (_, want) in zip(sorted(toks2.items()),
                                   sorted(base_toks.items())):
        np.testing.assert_array_equal(
            got, want, err_msg="speculative b2-draft diverged from the "
            "never-drafted baseline")
    speedup2 = base_best["us_per_tok"] / max(best2["us_per_tok"], 1e-9)
    _row("engine_decode_speculative_k4_b2draft", best2["us_per_tok"],
         f"accepted_tok_per_s={best2['tok_per_s']:.1f};"
         f"baseline_tok_per_s={base_best['tok_per_s']:.1f};"
         f"speedup={speedup2:.2f}x;"
         f"acceptance={best2['acceptance']:.2f};draft=s50/b2")
    results["k4_b2draft"] = {**best2, "speedup": speedup2,
                             "draft_bits": 2.0, "token_identical": True}

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_speculative.json")
    payload = {
        "bench": "engine_decode_speculative",
        "arch": "internlm2-1.8b(smoke)",
        "workload": {"slots": slots, "prompt_lens": lens, "gen": gen,
                     "target": "masked-checkpoint dense b8 (s50 groups "
                               "hard-zeroed)",
                     "draft": "same checkpoint, s50-sliced packed subnet"},
        "host_backend": jax.default_backend(),
        "rows": results,
    }
    with open(os.path.abspath(path), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def bench_engine_paged_kv(fast=False):
    """Paged + quantized KV arena (DESIGN.md §4.11): concurrency at a
    fixed KV HBM budget.

    Workload: requests sharing one hot "system prompt" (page-aligned),
    short per-request generations — the serving shape prefix sharing and
    page-granular allocation exist for. The contiguous arena pins
    max_seq rows per slot no matter what; the paged arena charges each
    request only its *owned* pages (the shared prompt is pinned once,
    refcounted), stored as int8 codes + per-row scales. The headline row
    divides the contiguous per-request bytes by the paged *marginal*
    per-request bytes (measured from the engine's own allocation
    accounting with every slot live) — how many more concurrent
    requests the same KV HBM holds — and asserts the ISSUE's >=2x. An
    unshared (all-distinct prompts) row isolates what quantization alone
    buys. Persists to BENCH_paged.json at the repo root."""
    import json
    import os

    from repro.launch.engine import build_engine, synthetic_prompts

    slots = 4
    sys_len, gen = 16, 8
    page_size = 8
    max_seq = sys_len + gen

    def admitted_kv_bytes(eng, prompts, n):
        # submit n requests and run exactly one engine step: every slot
        # admits (allocating its pages) and decodes once, so kv_bytes()
        # reads the arena with all n requests live
        for p in prompts[:n]:
            eng.submit(p, gen)
        eng.step()
        return eng.kv_bytes()

    contig, lm = build_engine("internlm2-1.8b", True, max_slots=slots,
                              max_seq=max_seq)
    per_req_contig = contig.kv_bytes() // slots
    _row("engine_paged_kv_contiguous_per_request", 0.0,
         f"bytes={per_req_contig};max_seq={max_seq}")

    def marginal(shared):
        eng, _ = build_engine("internlm2-1.8b", True, max_slots=slots,
                              max_seq=max_seq, paged=True,
                              page_size=page_size, kv_bits=8)
        prompts = synthetic_prompts(lm.cfg, [sys_len] * slots)
        if shared:
            prompts = [prompts[0].copy() for _ in prompts]
        eng.warmup()
        b1 = admitted_kv_bytes(eng, prompts, 1)
        bn = admitted_kv_bytes(eng, prompts[1:], slots - 1)
        eng.run()
        return (bn - b1) // (slots - 1), b1, eng

    per_req_shared, base_shared, eng_s = marginal(shared=True)
    _row("engine_paged_kv_paged_int8_shared_marginal", 0.0,
         f"bytes={per_req_shared};base={base_shared};"
         f"prefix_hits={eng_s.stats['prefix_hits']};"
         f"page_size={page_size}")
    per_req_unshared, base_unshared, _ = marginal(shared=False)
    _row("engine_paged_kv_paged_int8_unshared_marginal", 0.0,
         f"bytes={per_req_unshared};base={base_unshared}")

    # concurrency at the contiguous engine's own KV budget: how many
    # requests fit in the HBM the contiguous arena pins for `slots`
    budget = contig.kv_bytes()
    fit_paged = (budget - base_shared) // max(per_req_shared, 1) + 1
    concurrency_x = per_req_contig / max(per_req_shared, 1)
    _row("engine_paged_kv_concurrency", 0.0,
         f"{concurrency_x:.2f}x;contig_fits={slots};"
         f"paged_fits={fit_paged};budget={budget}")
    assert concurrency_x >= 2.0, (
        f"paged+int8+shared concurrency {concurrency_x:.2f}x < 2x")

    results = {
        "contiguous_per_request_bytes": int(per_req_contig),
        "paged_int8_shared_marginal_bytes": int(per_req_shared),
        "paged_int8_unshared_marginal_bytes": int(per_req_unshared),
        "paged_base_bytes_shared": int(base_shared),
        "paged_base_bytes_unshared": int(base_unshared),
        "kv_budget_bytes": int(budget),
        "requests_at_budget": {"contiguous": slots,
                               "paged_int8_shared": int(fit_paged)},
        "concurrency_x": float(concurrency_x),
        "prefix_hits": int(eng_s.stats["prefix_hits"]),
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_paged.json")
    payload = {
        "bench": "engine_paged_kv",
        "arch": "internlm2-1.8b(smoke)",
        "workload": {"slots": slots, "system_prompt_len": sys_len,
                     "gen": gen, "page_size": page_size, "kv_bits": 8},
        "host_backend": jax.default_backend(),
        "rows": results,
    }
    with open(os.path.abspath(path), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def bench_engine_tp(fast=False):
    """Tensor-parallel serving (DESIGN.md §4.12): engine decode at TP
    1 / 2 / 4 on the same weights/prompts/seed, plus the disaggregated
    chunked-prefill row.

    On a 1-device host only the tp=1 row runs; under
    `XLA_FLAGS=--xla_force_host_platform_device_count=4` the 2- and
    4-device rows appear. Fake CPU devices share the same cores, so
    `us_per_tok` measures GSPMD dispatch overhead, not a speedup — the
    quantities that transfer to hardware are the per-device param/KV
    bytes (the ~1/tp memory claim) and the token-identity assert (every
    TP arm must emit exactly the 1-device stream; the smoke arch has 2
    KV heads, so tp=4 shows the replicate-fallback: params still shrink,
    the arena doesn't). The chunked row serves a long prompt behind a
    short one and records how many decode steps ran mid-prefill — the
    head-of-line-blocking fix, asserted nonzero. Persists to
    BENCH_tp.json at the repo root."""
    import json
    import os

    from repro.launch.engine import build_engine, synthetic_prompts

    slots = 4
    gen = 12 if fast else 24
    lens = [6, 6, 6, 6]
    sizes = [n for n in (1, 2, 4) if n <= jax.device_count()]
    results = {}
    base_tokens = None
    for n in sizes:
        eng, lm = build_engine("internlm2-1.8b", True, max_slots=slots,
                               max_seq=max(lens) + gen, tp=n if n > 1 else 0)
        for p in synthetic_prompts(lm.cfg, lens):
            eng.submit(p, gen)
        eng.warmup()
        toks = eng.run()
        if base_tokens is None:
            base_tokens = toks
        else:
            for rid in base_tokens:
                np.testing.assert_array_equal(
                    toks[rid], base_tokens[rid],
                    err_msg=f"tp={n} decode diverged from 1-device")
        us = eng.stats["decode_s"] * 1e6 / max(eng.stats["decode_tokens"], 1)
        full_p, per_p = eng.param_bytes(), eng.param_bytes(per_device=True)
        full_k, per_k = eng.kv_bytes(), eng.kv_bytes(per_device=True)
        _row(f"engine_decode_tp_{n}dev", us,
             f"tok_per_s={eng.throughput()['decode_tok_per_s']:.1f};"
             f"param_bytes_per_dev={per_p};"
             f"param_shrink={full_p / max(per_p, 1):.2f}x;"
             f"kv_bytes_per_dev={per_k};"
             f"kv_shrink={full_k / max(per_k, 1):.2f}x;"
             f"token_identical={base_tokens is not None}")
        results[f"tp{n}"] = {
            "devices": n, "us_per_tok": us,
            "param_bytes_per_dev": int(per_p), "param_bytes": int(full_p),
            "kv_bytes_per_dev": int(per_k), "kv_bytes": int(full_k),
            "token_identical": True,
        }

    # disaggregated chunked prefill: a 40-token prompt prefills in chunks
    # of 8 behind an already-decoding short request; without chunking the
    # long prefill is one dispatch every active slot waits on
    chunk = 8
    eng, lm = build_engine("internlm2-1.8b", True, max_slots=2, max_seq=64,
                           prefill_chunk=chunk)
    prompts = synthetic_prompts(lm.cfg, [6, 40])
    eng.submit(prompts[0], 16 if fast else 32)
    eng.submit(prompts[1], 8)
    eng.warmup()
    eng.run()
    assert eng.stats["decode_steps_mid_prefill"] > 0, \
        "chunked prefill never interleaved a decode step"
    us = eng.stats["decode_s"] * 1e6 / max(eng.stats["decode_tokens"], 1)
    _row("engine_prefill_chunked", us,
         f"chunk={chunk};prefill_chunks={eng.stats['prefill_chunks']};"
         f"decode_steps_mid_prefill={eng.stats['decode_steps_mid_prefill']};"
         f"tok_per_s={eng.throughput()['decode_tok_per_s']:.1f}")
    results["chunked_prefill"] = {
        "chunk": chunk, "us_per_tok": us,
        "prefill_chunks": int(eng.stats["prefill_chunks"]),
        "decode_steps_mid_prefill":
            int(eng.stats["decode_steps_mid_prefill"]),
    }

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_tp.json")
    payload = {
        "bench": "engine_tp",
        "arch": "internlm2-1.8b(smoke)",
        "workload": {"slots": slots, "prompt_lens": lens, "gen": gen,
                     "prefill_chunk": chunk},
        "host_backend": jax.default_backend(),
        "rows": results,
    }
    with open(os.path.abspath(path), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def bench_sharded_train_scaling(fast=False):
    """1 -> N-device GETA train-step scaling (data-parallel, deterministic
    ordered reduction — DESIGN.md §5).

    On a 1-device host this prints the single-device row only; under
    `XLA_FLAGS=--xla_force_host_platform_device_count=N` it adds a row per
    mesh size. Fake CPU devices share the same cores, so `us_per_step`
    measures dispatch/partitioning overhead rather than real speedup —
    the `per_dev_batch` column is the quantity that scales on hardware."""
    from repro.configs import CompressionConfig, get_arch
    from repro.data.synthetic import batch_for
    from repro.launch.mesh import make_subset_mesh
    from repro.launch.specs import param_specs
    from repro.launch.train import build_geta, make_sharded_geta_train_step
    from repro.distributed.sharding import make_plan
    from repro.models.transformer import LM

    steps = 6 if fast else 20
    batch = 8
    comp = CompressionConfig(
        target_sparsity=0.25, warmup_steps=2, projection_periods=1,
        projection_steps=2, pruning_periods=2, pruning_steps=2,
        cooldown_steps=max(steps - 8, 2))
    n_dev = jax.device_count()
    sizes = sorted({1, n_dev} | ({2} if n_dev >= 2 else set()))
    sizes = [n for n in sizes if batch % n == 0]
    base_us = None
    out = {}
    for n in sizes:
        cfg = get_arch("internlm2-1.8b", smoke=True)
        lm = LM(cfg)
        params, _ = lm.init(jax.random.PRNGKey(0))
        qparams = lm.init_qparams(params, bits_init=16.0)
        _, qasso = build_geta(lm, comp, lr=3e-3, base_optimizer="momentum")
        qstate = qasso.init(params, qparams)
        mesh = make_subset_mesh(n)
        _, p_sh, _ = param_specs(lm, mesh, make_plan(mesh, fsdp=False))
        jstep, (psh, qsh, ssh, bsh) = make_sharded_geta_train_step(
            lm, qasso, mesh, params, qparams, param_shardings=p_sh,
            grad_slices=n)
        params = jax.device_put(params, psh)
        qparams = jax.device_put(qparams, qsh)
        qstate = jax.device_put(qstate, ssh)
        b0 = jax.device_put(batch_for(cfg, 0, 0, batch, 16), bsh)
        # warm the compile outside the timed loop
        params, qparams, qstate, m = jstep(params, qparams, qstate, b0)
        jax.block_until_ready(m["loss"])
        t0 = time.time()
        for i in range(1, steps):
            b = jax.device_put(batch_for(cfg, 0, i, batch, 16), bsh)
            params, qparams, qstate, m = jstep(params, qparams, qstate, b)
        jax.block_until_ready(m["loss"])
        us = (time.time() - t0) / max(steps - 1, 1) * 1e6
        base_us = base_us or us
        _row(f"sharded_geta_step_{n}dev", us,
             f"devices={n};per_dev_batch={batch//n};"
             f"rel_step_time={us/base_us:.2f};loss={float(m['loss']):.3f}")
        out[n] = us
    return out


def bench_static_analysis(fast=False):
    """§4.13: the static contract checker over the full serving matrix +
    trainer — wall time per analyzed entry (trace-only, no compiles) and
    the finding counts the CI gate sees."""
    from repro.analysis import passes, registry, report

    t0 = time.time()
    engines, traced = registry.build_serving()
    traced = traced + [registry.build_training()]
    findings = passes.run_all(engines, traced)
    wall = time.time() - t0
    base = report.load_baseline()
    new, sup = report.split_findings(findings, base)
    _row("static_analysis_full_matrix", wall * 1e6 / max(len(traced), 1),
         f"entries={len(traced)};groups={len(engines)};wall_s={wall:.1f};"
         f"findings={len(findings)};new={len(new)};suppressed={len(sup)}")
    return wall


ALL = [bench_table2_resnet20, bench_table3_bert, bench_table4_vgg7,
       bench_table5_resnet56, bench_fig4a_ablation, bench_fig4b_frontier,
       bench_kernel_fake_quant, bench_kernel_fused_joint, bench_serve_decode,
       bench_engine_prefill, bench_engine_continuous,
       bench_engine_decode_pruned, bench_engine_decode_packed,
       bench_engine_decode_attn, bench_engine_decode_speculative,
       bench_engine_paged_kv, bench_engine_tp, bench_sharded_train_scaling,
       bench_static_analysis]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced steps/sweeps (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    failed = []
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn(fast=args.fast)
        except Exception as e:  # report, keep the harness going
            _row(fn.__name__ + "_FAILED", 0.0, f"{type(e).__name__}:{e}")
            failed.append(fn.__name__)
    if failed:
        sys.exit(f"{len(failed)} benchmark(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
