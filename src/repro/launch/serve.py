"""Serving CLI: continuous-batching engine by default, static loop kept as
the lockstep reference path.

Two weight paths (both modes):
  default       — dense params; weight-quant sites applied as fake-quant
                  (QAT numerics, f32/bf16 weights in HBM).
  --compressed  — the deployment path: projection weights are replaced by a
                  `Subnet`'s integer codes + scales (`core.subnet`), and
                  every routed matmul decodes them through the quant-dequant
                  epilogue on the shared GEMM core (int8 streams HBM->VMEM,
                  `codes * scale` inside VMEM). This is the paper's BOPs
                  claim actually executed, not just counted.
  --packed      — sub-byte storage on top of --compressed (implied): codes
                  bit-pack along K into int32 word streams at each site's
                  learned storage width (2/3/4/8), decoded in VMEM by the
                  unpack-dequant epilogue — a 4-bit site moves half the
                  HBM bytes of its int8 container (DESIGN.md §4.8).

Two execution modes:
  engine (default) — `launch.engine.Engine`: request queue with
                  admission/eviction, slot-based KV arena, per-slot decode
                  positions, one-shot parallel prefill. `--prompt-lens`
                  takes per-request prompt lengths (mixed lengths are the
                  point).
  --static      — the legacy `serve_loop`: one fixed batch in lockstep with
                  a sequential per-token prefill. Kept as the engine's
                  parity oracle (tests/test_engine.py) and the benchmark
                  baseline.

Reduced-scale smoke (runs here):
  PYTHONPATH=src python -m repro.launch.serve --smoke --compressed \
      --prompt-lens 12,5 --gen 8
  PYTHONPATH=src python -m repro.launch.serve --smoke --static \
      --batch 4 --prompt-len 16 --gen 32 [--compressed]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.subnet import compression_report, prepare_serving
from repro.data.synthetic import batch_for
from repro.models.transformer import LM


def make_serve_step(lm: LM):
    def serve_step(params, qparams, caches, token, pos):
        logits, caches = lm.decode_step(params, qparams, caches, token, pos)
        if lm.cfg.num_codebooks:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            nxt = nxt[:, None, :]    # (B, 1, C)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            nxt = nxt[:, None]
        return nxt, caches

    return serve_step


def serve_loop(arch: str, smoke: bool, batch: int, prompt_len: int,
               gen: int, seed: int = 0, quantized: bool = True,
               compressed: bool = False, packed: bool = False,
               pruned: bool = False, sparsity: float = 0.5,
               bits_init: float = 8.0, verbose: bool = True,
               stats: dict | None = None, prompts=None):
    """Static lockstep reference: decode `gen` tokens after a *sequential*
    per-token prefill; returns the (batch, gen) token matrix. If `stats`
    is given it receives decode-only timing (the prefill warms the jit, so
    compile/init never pollute it). `prompts` overrides the synthetic
    (batch, prompt_len) prompt matrix — `tests/test_engine.py` feeds the
    identical requests through this loop and the engine with it. `pruned`
    decodes the physically sliced subnet at magnitude masks of `sparsity`
    (the shrunk KV arena included)."""
    cfg = get_arch(arch, smoke=smoke)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(seed))
    params, qparams, meta = prepare_serving(
        lm, params, quantized=quantized, compressed=compressed,
        packed=packed, bits_init=bits_init,
        prune_sparsity=(sparsity if pruned else None))
    if (compressed or packed or pruned) and verbose:
        print(compression_report(arch, meta))
    if prompts is None:
        prompts = batch_for(cfg, seed, 0, batch, prompt_len)["tokens"]
        if cfg.family == "vlm":
            prompts = prompts[:, :prompt_len]
    prompt = jnp.asarray(prompts)
    prompt_len = prompt.shape[1]   # an explicit matrix sets the length

    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    caches = lm.init_cache(batch, prompt_len + gen, dtype=dt)
    step = jax.jit(make_serve_step(lm))

    # prefill via sequential decode (cache-building path)
    tok = prompt[:, :1]
    for p in range(prompt_len):
        tok = prompt[:, p:p + 1]
        nxt, caches = step(params, qparams, caches, tok, jnp.int32(p))
    out = [nxt]
    t0 = time.time()
    for g in range(gen - 1):
        nxt, caches = step(params, qparams, caches, out[-1],
                           jnp.int32(prompt_len + g))
        out.append(nxt)
    jax.block_until_ready(out[-1])
    dt_s = time.time() - t0
    toks = batch * (gen - 1)
    if stats is not None:
        stats.update(decode_s=dt_s, tokens=toks,
                     tok_per_s=toks / max(dt_s, 1e-9))
    if verbose:
        mode = "compressed" if (compressed or packed) else "dense"
        if packed:
            mode += "+packed"
        print(f"{arch} [static/{mode}]: generated {toks} tokens in "
              f"{dt_s:.2f}s ({toks/max(dt_s,1e-9):.1f} tok/s, "
              f"batch={batch})")
    seq = jnp.concatenate(out, axis=1)
    return seq


def pruned_parity_check(arch: str, smoke: bool, prompt_lens: list[int],
                        gen: int, *, sparsity: float, quantized: bool,
                        compressed: bool = False, max_slots: int,
                        seed: int = 0, verbose: bool = True) -> dict:
    """Assert the pruned engine's decode is token-identical to the masked
    dense reference (same seed, masks and quantizer init; zeroed units
    contribute exact zeros, so slicing them away must not change a single
    greedy token). Raises AssertionError on divergence — this is the CI
    smoke for `serve --pruned --smoke`. Returns the pruned engine's
    output, so the caller reports throughput without decoding a second
    engine."""
    import numpy as np

    from repro.launch.engine import (build_masked_reference_engine,
                                     engine_serve, synthetic_prompts)
    max_seq = max(prompt_lens) + gen
    # `compressed` implies quantization on the pruned arm (prepare_serving
    # resolves qparams either way), so the reference must quantize too or
    # the two arms would run different numerics under --no-quant
    ref, lm = build_masked_reference_engine(
        arch, smoke, sparsity=sparsity,
        quantized=(quantized or compressed),
        max_slots=max_slots, max_seq=max_seq, seed=seed)
    for p in synthetic_prompts(lm.cfg, prompt_lens, seed):
        ref.submit(p, gen)
    want = ref.run()
    got = engine_serve(arch, smoke, prompt_lens, gen, quantized=quantized,
                       compressed=compressed, pruned=True, sparsity=sparsity,
                       max_slots=max_slots, seed=seed, verbose=verbose)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"pruned decode diverged from masked reference "
                    f"(request {rid})")
    print(f"{arch}: pruned decode (sparsity {sparsity:.2f}) token-identical "
          f"to the masked dense reference over {len(want)} requests")
    return got


def packed_parity_check(arch: str, smoke: bool, prompt_lens: list[int],
                        gen: int, *, pruned: bool = False,
                        sparsity: float = 0.5, bits_init: float = 8.0,
                        max_slots: int, seed: int = 0,
                        verbose: bool = True) -> dict:
    """Assert the packed engine's decode is token-identical to the
    unpacked int8 path. `unpack_codes(pack_codes(c, b), b)` is exact and
    both arms share seed, scales and clamped codes, so the dequantized
    weights — and every greedy token — must match bit-for-bit; a packing
    or sign-extension regression shows up as divergence here. Stacks with
    `pruned` (both arms then serve the same sliced shapes). Raises
    AssertionError on divergence — the CI smoke for `serve --packed
    --smoke`. Returns the packed engine's output (the serving run that
    printed the throughput report)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    want = engine_serve(arch, smoke, prompt_lens, gen, compressed=True,
                        packed=False, pruned=pruned, sparsity=sparsity,
                        bits_init=bits_init, max_slots=max_slots, seed=seed,
                        verbose=False)
    got = engine_serve(arch, smoke, prompt_lens, gen, compressed=True,
                       packed=True, pruned=pruned, sparsity=sparsity,
                       bits_init=bits_init, max_slots=max_slots, seed=seed,
                       verbose=verbose)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"packed decode diverged from the unpacked int8 "
                    f"reference (request {rid})")
    print(f"{arch}: packed decode token-identical to the unpacked int8 "
          f"path over {len(want)} requests"
          + (f" (pruned @ {sparsity:.2f})" if pruned else ""))
    return got


def decode_attn_parity_check(arch: str, smoke: bool, prompt_lens: list[int],
                             gen: int, *, compressed: bool = False,
                             packed: bool = False, pruned: bool = False,
                             sparsity: float = 0.5, bits_init: float = 8.0,
                             max_slots: int, seed: int = 0,
                             verbose: bool = True) -> dict:
    """Assert engine decode with the fused flash-decode attention kernel
    is token-identical to the legacy full-length einsum path, on the same
    weights/prompts/seed. Both arms share every GEMM; only the decode
    attention composition differs, and the kernel's xla-ref backend runs
    the einsum math bit-for-bit (ref.decode_attn_ref) while the Pallas
    backends agree to the parity tier's 1e-4 — so greedy tokens must
    match exactly on any host. Stacks with --pruned / --packed (the
    kernel is parameterized by LayerShapes, so sliced head counts flow
    through). Raises AssertionError on divergence — the CI smoke for
    `serve --smoke --decode-attn-parity`. Returns the kernel arm's
    output (the run that printed the throughput report)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    want = engine_serve(arch, smoke, prompt_lens, gen,
                        compressed=compressed, packed=packed, pruned=pruned,
                        sparsity=sparsity, bits_init=bits_init,
                        max_slots=max_slots, seed=seed, verbose=False,
                        decode_attn=False)
    got = engine_serve(arch, smoke, prompt_lens, gen,
                       compressed=compressed, packed=packed, pruned=pruned,
                       sparsity=sparsity, bits_init=bits_init,
                       max_slots=max_slots, seed=seed, verbose=verbose,
                       decode_attn=True)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"flash-decode attention diverged from the einsum "
                    f"reference path (request {rid})")
    mode = ("packed" if packed else
            "compressed" if compressed else "dense")
    if pruned:
        mode += f"+pruned@{sparsity:.2f}"
    print(f"{arch}: flash-decode attention token-identical to the einsum "
          f"reference over {len(want)} requests ({mode})")
    return got


def speculative_parity_check(arch: str, smoke: bool,
                             prompt_lens: list[int], gen: int, *,
                             quantized: bool = True,
                             compressed: bool = False, packed: bool = False,
                             pruned: bool = False, sparsity: float = 0.5,
                             bits_init: float = 8.0, draft_k: int = 4,
                             draft_sparsity: float = 0.5,
                             draft_bits: float = 2.0, max_slots: int,
                             seed: int = 0, verbose: bool = True) -> dict:
    """Assert the speculative engine's decode is token-identical to the
    non-speculative engine on the same target weights/prompts/seed.

    The draft/verify loop commits only the *target's* argmaxes (the
    accepted prefix plus the verify pass's free token), so identity is
    the protocol's structural guarantee — any divergence means the
    rollback or position bookkeeping corrupted the target arena, which
    is exactly what this smoke exists to catch. The draft config is
    deliberately aggressive (s50 + b2 by default): a near-zero-acceptance
    draft maximizes rollback traffic. Raises AssertionError on
    divergence — the CI smoke for `serve --speculative --smoke`. Returns
    the speculative arm's output (the run that printed the report)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    want = engine_serve(arch, smoke, prompt_lens, gen, quantized=quantized,
                        compressed=compressed, packed=packed, pruned=pruned,
                        sparsity=sparsity, bits_init=bits_init,
                        max_slots=max_slots, seed=seed, verbose=False)
    got = engine_serve(arch, smoke, prompt_lens, gen, quantized=quantized,
                       compressed=compressed, packed=packed, pruned=pruned,
                       sparsity=sparsity, bits_init=bits_init,
                       max_slots=max_slots, seed=seed, verbose=verbose,
                       speculative=True, draft_k=draft_k,
                       draft_sparsity=draft_sparsity, draft_bits=draft_bits)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"speculative decode diverged from the "
                    f"non-speculative engine (request {rid})")
    print(f"{arch}: speculative decode (draft k={draft_k}, "
          f"s{100 * draft_sparsity:.0f}/b{draft_bits:.0f}) token-identical "
          f"to the non-speculative engine over {len(want)} requests")
    return got


def paged_parity_check(arch: str, smoke: bool, prompt_lens: list[int],
                       gen: int, *, quantized: bool = True,
                       compressed: bool = False, packed: bool = False,
                       pruned: bool = False, sparsity: float = 0.5,
                       bits_init: float = 8.0, speculative: bool = False,
                       draft_k: int = 4, draft_sparsity: float = 0.5,
                       draft_bits: float = 2.0, page_size: int = 16,
                       max_slots: int, seed: int = 0,
                       verbose: bool = True) -> dict:
    """Assert the paged engine's decode is token-identical to the
    contiguous-arena engine on the same weights/prompts/seed.

    The paged arena changes only *where* KV rows live (page pools behind
    per-slot page tables, prefix-shared pages, zero-page backing) — every
    gathered view is sliced back to the exact max_seq row count the
    contiguous engine reduces over, and prefix sharing only ever reuses
    bitwise-identical whole-prompt pages, so greedy tokens must match
    bit-for-bit. Stacks with --pruned/--packed/--speculative (the page
    pools take the sliced KV shapes; the draft arena pages through the
    same tables). Raises AssertionError on divergence — the CI smoke for
    `serve --paged --smoke`. Returns the paged arm's output (the run
    that printed the throughput report)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    common = dict(quantized=quantized, compressed=compressed, packed=packed,
                  pruned=pruned, sparsity=sparsity, bits_init=bits_init,
                  speculative=speculative, draft_k=draft_k,
                  draft_sparsity=draft_sparsity, draft_bits=draft_bits,
                  max_slots=max_slots, seed=seed)
    want = engine_serve(arch, smoke, prompt_lens, gen, verbose=False,
                        **common)
    got = engine_serve(arch, smoke, prompt_lens, gen, verbose=verbose,
                       paged=True, page_size=page_size, **common)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"paged decode diverged from the contiguous arena "
                    f"(request {rid})")
    mode = ("packed" if packed else
            "compressed" if compressed else "dense")
    if pruned:
        mode += f"+pruned@{sparsity:.2f}"
    if speculative:
        mode += f"+spec(k={draft_k})"
    print(f"{arch}: paged KV decode (page_size={page_size}) "
          f"token-identical to the contiguous arena over {len(want)} "
          f"requests ({mode})")
    return got


def tp_parity_check(arch: str, smoke: bool, prompt_lens: list[int],
                    gen: int, *, tp: int, quantized: bool = True,
                    compressed: bool = False, packed: bool = False,
                    pruned: bool = False, sparsity: float = 0.5,
                    bits_init: float = 8.0, speculative: bool = False,
                    draft_k: int = 4, draft_sparsity: float = 0.5,
                    draft_bits: float = 2.0, paged: bool = False,
                    page_size: int = 16, prefill_chunk: int | None = None,
                    max_slots: int, seed: int = 0,
                    verbose: bool = True) -> dict:
    """Assert the tensor-parallel engine's decode is token-identical to
    the single-device engine on the same weights/prompts/seed.

    TP sharding is column/head-parallel by construction (DESIGN.md
    §4.12): every output column and KV head lives wholly on one device,
    so no contraction is ever split across devices and no cross-device
    reduction reassociates a sum — greedy argmaxes must match bit for
    bit, across the whole compression stack. Raises AssertionError on
    divergence — the CI smoke for `serve --tp N --smoke`. Returns the TP
    arm's output (the run that printed the throughput report), and
    reports any shapes the mesh couldn't divide (replication
    fallbacks)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    common = dict(quantized=quantized, compressed=compressed, packed=packed,
                  pruned=pruned, sparsity=sparsity, bits_init=bits_init,
                  speculative=speculative, draft_k=draft_k,
                  draft_sparsity=draft_sparsity, draft_bits=draft_bits,
                  paged=paged, page_size=page_size,
                  prefill_chunk=prefill_chunk, max_slots=max_slots,
                  seed=seed)
    want = engine_serve(arch, smoke, prompt_lens, gen, verbose=False,
                        **common)
    st: dict = {}
    got = engine_serve(arch, smoke, prompt_lens, gen, verbose=verbose,
                       tp=tp, stats=st, **common)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"tp={tp} decode diverged from the single-device "
                    f"engine (request {rid})")
    mode = ("packed" if packed else
            "compressed" if compressed else "dense")
    if pruned:
        mode += f"+pruned@{sparsity:.2f}"
    if paged:
        mode += "+paged"
    if speculative:
        mode += f"+spec(k={draft_k})"
    print(f"{arch}: tp={tp} decode token-identical to the single-device "
          f"engine over {len(want)} requests ({mode})")
    return got


def chunked_prefill_parity_check(arch: str, smoke: bool,
                                 prompt_lens: list[int], gen: int, *,
                                 prefill_chunk: int, quantized: bool = True,
                                 compressed: bool = False,
                                 packed: bool = False, pruned: bool = False,
                                 sparsity: float = 0.5,
                                 bits_init: float = 8.0, tp: int = 0,
                                 max_slots: int, seed: int = 0,
                                 verbose: bool = True) -> dict:
    """Assert the chunked-prefill engine's decode is token-identical to
    the one-shot-prefill engine, and that decode actually ran while a
    prefill was in flight (`decode_steps_mid_prefill > 0` whenever a
    multi-chunk prompt and an active slot coexisted) — the
    disaggregation is only worth its machinery if both hold. Raises
    AssertionError on divergence — the CI smoke for
    `serve --chunked-prefill N --smoke`. Returns the chunked arm's
    output (the run that printed the throughput report)."""
    import numpy as np

    from repro.launch.engine import engine_serve
    common = dict(quantized=quantized, compressed=compressed, packed=packed,
                  pruned=pruned, sparsity=sparsity, bits_init=bits_init,
                  tp=tp, max_slots=max_slots, seed=seed)
    want = engine_serve(arch, smoke, prompt_lens, gen, verbose=False,
                        **common)
    st: dict = {}
    got = engine_serve(arch, smoke, prompt_lens, gen, verbose=verbose,
                       prefill_chunk=prefill_chunk, stats=st, **common)
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"chunked prefill (chunk={prefill_chunk}) diverged "
                    f"from the one-shot engine (request {rid})")
    if len(prompt_lens) > 1 and any(n > prefill_chunk
                                    for n in prompt_lens[1:]):
        # a later prompt needed several chunks while request 0 decoded,
        # so disaggregation must have interleaved at least once
        assert st["decode_steps_mid_prefill"] > 0, st
    print(f"{arch}: chunked prefill (chunk={prefill_chunk}) "
          f"token-identical to the one-shot engine over {len(want)} "
          f"requests; {st['prefill_chunks']} chunks, "
          f"{st['decode_steps_mid_prefill']} decode steps ran mid-prefill")
    return got


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--static", action="store_true", default=False,
                    help="legacy lockstep serve_loop instead of the "
                         "continuous-batching engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="static mode: lockstep batch size")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="static mode: shared prompt length")
    ap.add_argument("--prompt-lens", default=None,
                    help="engine mode: comma-separated per-request prompt "
                         "lengths, e.g. 16,4,9 (default: --batch requests "
                         "of --prompt-len each)")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine mode: decode slots (concurrent requests)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--no-quant", dest="quantized", action="store_false",
                    default=True)
    ap.add_argument("--compressed", action="store_true", default=False,
                    help="decode from Subnet int codes via the quant-dequant "
                         "GEMM epilogue instead of dense params (implies "
                         "quantization; overrides --no-quant)")
    ap.add_argument("--packed", action="store_true", default=False,
                    help="store the codes as sub-byte packed int32 word "
                         "streams and decode through the unpack-dequant "
                         "epilogue (implies --compressed); in --smoke mode "
                         "also asserts decode tokens are identical to the "
                         "unpacked int8 path")
    ap.add_argument("--bits", type=float, default=8.0,
                    help="quantizer init width: the learned per-site bit "
                         "widths start here, so --packed --bits 4 serves a "
                         "genuinely 4-bit artifact (half the int8 container "
                         "bytes; 2 -> a quarter)")
    ap.add_argument("--pruned", action="store_true", default=False,
                    help="physically slice the model to magnitude masks at "
                         "--sparsity and serve the pruned shapes (smaller "
                         "GEMMs + shrunk KV arena); in --smoke mode also "
                         "asserts decode tokens are identical to the masked "
                         "dense reference")
    ap.add_argument("--sparsity", type=float, default=0.5,
                    help="pruned mode: target fraction of prunable units "
                         "removed (default 0.5)")
    ap.add_argument("--speculative", action="store_true", default=False,
                    help="engine mode: self-speculative decoding — a "
                         "pruned+packed subnet of the same checkpoint "
                         "drafts up to --draft-k tokens per round and the "
                         "target verifies them in one chunked pass; output "
                         "tokens are always the target's (in --smoke mode "
                         "also asserts token identity vs the "
                         "non-speculative engine)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative mode: max draft proposals per round")
    ap.add_argument("--draft-sparsity", type=float, default=50.0,
                    help="speculative mode: draft subnet sparsity — a "
                         "percentage (50) or fraction (0.5); 0 keeps all "
                         "units (packed-only draft)")
    ap.add_argument("--draft-bits", type=float, default=2.0,
                    help="speculative mode: draft quantizer init width "
                         "(packed storage bits)")
    ap.add_argument("--paged", action="store_true", default=False,
                    help="engine mode: paged KV arena — fixed-size KV "
                         "pages in one pool behind per-slot page tables, "
                         "with whole-prompt prefix sharing (repeated "
                         "prompts share refcounted pages and skip their "
                         "prefill); in --smoke mode also asserts decode "
                         "tokens are identical to the contiguous arena "
                         "(DESIGN.md §4.11)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged mode: KV rows per page (multiple of 8)")
    ap.add_argument("--kv-bits", type=int, default=None,
                    choices=[4, 8],
                    help="paged mode: quantize the page store to int8 or "
                         "nibble-packed int4 codes + per-row scales, "
                         "decoded in-VMEM by the flash-decode kernel "
                         "(approximate numerics: skips the --smoke "
                         "token-identity check)")
    ap.add_argument("--tp", type=int, default=0,
                    help="engine mode: tensor-parallel serving over a "
                         "(1, N) device mesh — params shard by attention "
                         "head / MLP hidden / vocab, the KV arena by KV "
                         "head (DESIGN.md §4.12); in --smoke mode also "
                         "asserts decode tokens are identical to the "
                         "single-device engine")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU runs (JAX_PLATFORMS=cpu) only: force N XLA "
                         "host devices before the backend initializes so "
                         "--tp runs on a single-CPU host; ignored on an "
                         "accelerator host, whose chips form the mesh")
    ap.add_argument("--chunked-prefill", type=int, default=None,
                    metavar="CHUNK",
                    help="engine mode: split each prompt's prefill into "
                         "CHUNK-row chunks interleaved with decode steps "
                         "(disaggregated prefill/decode — long prompts "
                         "stop head-of-line-blocking active slots); in "
                         "--smoke mode also asserts decode tokens are "
                         "identical to the one-shot engine")
    ap.add_argument("--no-decode-attn", dest="decode_attn",
                    action="store_false", default=True,
                    help="disable the fused flash-decode attention kernel "
                         "and decode through the legacy full-length "
                         "einsum+softmax path (DESIGN.md §4.9)")
    ap.add_argument("--decode-attn-parity", action="store_true",
                    default=False,
                    help="engine mode: serve twice — flash-decode kernel "
                         "forced on and forced off — and assert the greedy "
                         "tokens are identical (the decode-attn CI smoke; "
                         "honors --compressed/--packed/--pruned)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    from repro.launch.mesh import force_host_devices
    force_host_devices(args.devices)
    compile_cache.enable()
    if not args.decode_attn:
        from repro.models.layers import set_decode_attn
        set_decode_attn(False)
    cfg = get_arch(args.arch, smoke=args.smoke)
    if not args.static and (cfg.num_codebooks or cfg.vision_patches):
        # the engine serves plain token LMs; these archs keep working
        # through the lockstep loop exactly as before this CLI existed
        print(f"{args.arch}: codebook/VLM prompts need a modality "
              f"frontend — falling back to the static loop")
        args.static = True
    if args.static:
        serve_loop(args.arch, args.smoke, args.batch, args.prompt_len,
                   args.gen, quantized=args.quantized,
                   compressed=args.compressed, packed=args.packed,
                   pruned=args.pruned, sparsity=args.sparsity,
                   bits_init=args.bits)
        return
    from repro.launch.engine import engine_serve
    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len] * args.batch
    # --kv-bits quantizes the *paged* page store; asking for it implies
    # the paged arena rather than erroring on a flag the user clearly
    # wanted to take effect
    if args.kv_bits is not None:
        args.paged = True
    # `--draft-sparsity 50` and `--draft-sparsity 0.5` mean the same thing
    draft_sparsity = (args.draft_sparsity / 100.0
                      if args.draft_sparsity > 1.0 else args.draft_sparsity)
    if args.tp and args.tp > 1 and args.smoke:
        # CI smoke contract: N-device decode == 1-device decode, token
        # for token, across whatever compression/paged/speculative stack
        # is active — the `serve --tp --smoke` parity step.
        tp_parity_check(args.arch, args.smoke, lens, args.gen,
                        tp=args.tp, quantized=args.quantized,
                        compressed=args.compressed, packed=args.packed,
                        pruned=args.pruned, sparsity=args.sparsity,
                        bits_init=args.bits, speculative=args.speculative,
                        draft_k=args.draft_k, draft_sparsity=draft_sparsity,
                        draft_bits=args.draft_bits, paged=args.paged,
                        page_size=args.page_size,
                        prefill_chunk=args.chunked_prefill,
                        max_slots=args.slots)
        return
    if args.chunked_prefill and args.smoke:
        # CI smoke contract: chunked prefill == one-shot prefill, token
        # for token, AND decode steps demonstrably ran mid-prefill.
        chunked_prefill_parity_check(
            args.arch, args.smoke, lens, args.gen,
            prefill_chunk=args.chunked_prefill, quantized=args.quantized,
            compressed=args.compressed, packed=args.packed,
            pruned=args.pruned, sparsity=args.sparsity,
            bits_init=args.bits, tp=args.tp, max_slots=args.slots)
        return
    if args.paged and args.smoke and args.kv_bits is None:
        # CI smoke contract: paged decode == contiguous decode, token for
        # token, across whatever compression/speculative stack is active.
        # Quantized pages (--kv-bits) are deliberately lossy, so they
        # serve without the identity assertion.
        paged_parity_check(args.arch, args.smoke, lens, args.gen,
                           quantized=args.quantized,
                           compressed=args.compressed, packed=args.packed,
                           pruned=args.pruned, sparsity=args.sparsity,
                           bits_init=args.bits,
                           speculative=args.speculative,
                           draft_k=args.draft_k,
                           draft_sparsity=draft_sparsity,
                           draft_bits=args.draft_bits,
                           page_size=args.page_size, max_slots=args.slots)
        return
    if args.speculative and args.smoke:
        # CI smoke contract: speculative decode == non-speculative decode,
        # token for token (the draft only sets speed). The speculative arm
        # *is* the serving run, so nothing decodes twice.
        speculative_parity_check(args.arch, args.smoke, lens, args.gen,
                                 quantized=args.quantized,
                                 compressed=args.compressed,
                                 packed=args.packed, pruned=args.pruned,
                                 sparsity=args.sparsity,
                                 bits_init=args.bits, draft_k=args.draft_k,
                                 draft_sparsity=draft_sparsity,
                                 draft_bits=args.draft_bits,
                                 max_slots=args.slots)
        return
    if args.decode_attn_parity:
        # CI smoke contract: flash-decode kernel == einsum reference,
        # token for token. The kernel arm *is* the serving run (it prints
        # the throughput report), so nothing decodes a third time.
        decode_attn_parity_check(args.arch, args.smoke, lens, args.gen,
                                 compressed=args.compressed,
                                 packed=args.packed, pruned=args.pruned,
                                 sparsity=args.sparsity,
                                 bits_init=args.bits,
                                 max_slots=args.slots)
        return
    if args.packed and args.smoke:
        # CI smoke contract: packed decode == unpacked int8 decode, token
        # for token (stacks with --pruned: both arms slice first). The
        # packed arm *is* the serving run, so nothing decodes twice.
        packed_parity_check(args.arch, args.smoke, lens, args.gen,
                            pruned=args.pruned, sparsity=args.sparsity,
                            bits_init=args.bits, max_slots=args.slots)
        return
    if args.pruned and args.smoke:
        # CI smoke contract: pruned decode == masked dense reference,
        # token for token. The check's pruned arm *is* the serving run
        # (it prints the throughput report), so nothing decodes twice.
        pruned_parity_check(args.arch, args.smoke, lens, args.gen,
                            sparsity=args.sparsity,
                            quantized=args.quantized,
                            compressed=args.compressed,
                            max_slots=args.slots)
        return
    engine_serve(args.arch, args.smoke, lens, args.gen,
                 quantized=args.quantized, compressed=args.compressed,
                 packed=args.packed, pruned=args.pruned,
                 sparsity=args.sparsity, bits_init=args.bits,
                 max_slots=args.slots, speculative=args.speculative,
                 draft_k=args.draft_k, draft_sparsity=draft_sparsity,
                 draft_bits=args.draft_bits, paged=args.paged,
                 page_size=args.page_size, kv_bits=args.kv_bits,
                 tp=args.tp, prefill_chunk=args.chunked_prefill)


if __name__ == "__main__":
    main()
