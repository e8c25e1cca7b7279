"""Drive the continuous-batching engine over mixed-length requests.

Submits a handful of requests with different prompt lengths and token
budgets, drains the engine, and prints each request's generated tokens
plus the throughput counters (decode tok/s, one-shot prefill tok/s, slot
occupancy). `--compressed` serves from Subnet int8 codes through the
quant-dequant GEMM epilogue; `--packed` bit-packs the codes at their
learned sub-byte storage widths (unpack-dequant epilogue, DESIGN.md
§4.8); `--pruned` physically slices the model to magnitude masks first
(surviving heads / MLP hidden / experts only — the GEMMs and the KV
arena shrink with realized sparsity). Stacked, they are the full
deployment path: sub-byte codes at pruned shapes. `--speculative`
attaches the self-speculative draft — the same checkpoint sliced to
`--draft-sparsity` and packed at `--draft-bits` proposes up to
`--draft-k` tokens per round, the target verifies them in one chunked
pass, and the output stream stays token-identical to the plain engine
(DESIGN.md §4.10); the report line adds the acceptance rate.
`--paged` swaps the per-slot contiguous KV arena for the paged block
arena (DESIGN.md §4.11): per-request KV is page-granular, identical
prompts share refcounted pages and skip their prefill (`--hot-prompt`
sends every request the same prompt — watch `prefix_hits`), and
`--kv-bits 8|4` stores the pages as int8/nibble-packed codes
dequantized in-VMEM by the flash-decode kernel. `--tp N` shards the
whole stack over an N-device mesh — attention heads, MLP hidden, vocab,
and the KV arena's head axis — token-identical to 1 device with
per-device param/KV bytes ~1/N (`--devices N` with JAX_PLATFORMS=cpu
forces N fake host devices for trying this on a CPU box);
`--chunked-prefill C` prefills
prompts at most C rows per step into a staging row so decode keeps
running mid-prefill (DESIGN.md §4.12).

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_engine.py \
        --devices 4 --tp 4 --packed --bits 4 --prompt-lens 16,4,9,12 \
        --gens 12 --slots 2

    PYTHONPATH=src python examples/serve_engine.py --chunked-prefill 8 \
        --prompt-lens 6,40 --gens 24,8 --slots 2

    PYTHONPATH=src python examples/serve_engine.py --packed --pruned \
        --bits 4 --prompt-lens 16,4,9,12 --gens 24,8,16,12 --slots 2

    PYTHONPATH=src python examples/serve_engine.py --speculative \
        --draft-k 4 --draft-sparsity 0 --draft-bits 8 \
        --prompt-lens 16,4,9,12 --gens 24,8,16,12 --slots 2

    PYTHONPATH=src python examples/serve_engine.py --paged --kv-bits 8 \
        --hot-prompt --prompt-lens 16,16,16,9 --gens 12 --slots 2

(On these random-init smoke weights only a keep-all draft tracks the
target — `--draft-sparsity 0` shows acceptance ~1.0. A GETA cooldown
checkpoint, whose pruned groups are already zero, gets the same
acceptance from its s50 slice: `launch.speculative.
build_checkpoint_engines` and `BENCH_speculative.json` cover that pair.)
"""
import argparse

from repro.launch.engine import build_engine, synthetic_prompts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--prompt-lens", default="16,4,9,12",
                    help="comma-separated per-request prompt lengths")
    ap.add_argument("--gens", default="24,8,16,12",
                    help="comma-separated per-request token budgets "
                         "(a single value broadcasts)")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--no-quant", dest="quant", action="store_false",
                    default=True)
    ap.add_argument("--compressed", action="store_true", default=False,
                    help="decode from Subnet int codes (quant-dequant GEMM "
                         "epilogue) instead of dense weights")
    ap.add_argument("--packed", action="store_true", default=False,
                    help="bit-pack the codes at each site's learned storage "
                         "width (2/3/4/8) and decode via the unpack-dequant "
                         "epilogue (implies --compressed)")
    ap.add_argument("--bits", type=float, default=8.0,
                    help="quantizer init width (e.g. 4 serves a genuinely "
                         "4-bit packed artifact)")
    ap.add_argument("--pruned", action="store_true", default=False,
                    help="physically slice the model to magnitude masks at "
                         "--sparsity and serve the pruned shapes (smaller "
                         "GEMMs, shrunk KV arena); stacks with --compressed")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--speculative", action="store_true", default=False,
                    help="draft/verify decoding: a sliced+packed subnet of "
                         "the same checkpoint drafts tokens, the target "
                         "verifies them in one chunked pass — "
                         "token-identical output, fewer target dispatches")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max proposals per speculative round")
    ap.add_argument("--draft-sparsity", type=float, default=0.5,
                    help="draft subnet sparsity (0 keeps all units)")
    ap.add_argument("--draft-bits", type=float, default=8.0,
                    help="draft quantizer width (8 tracks the target "
                         "closely; 2 is cheap but rarely accepted)")
    ap.add_argument("--paged", action="store_true", default=False,
                    help="paged KV arena: page-granular allocation + "
                         "whole-prompt prefix sharing (DESIGN.md §4.11)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged mode: KV rows per page (multiple of 8)")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[4, 8],
                    help="paged mode: int8/int4 page store (implies "
                         "--paged; approximate numerics)")
    ap.add_argument("--hot-prompt", action="store_true", default=False,
                    help="requests with equal --prompt-lens entries send "
                         "the *identical* prompt (prefixes of the first "
                         "request's tokens) — the prefix-sharing demo: "
                         "repeats admit with prefix_hits, no prefill")
    ap.add_argument("--tp", type=int, default=0,
                    help="shard the engine over a tp-device mesh "
                         "(attention heads / MLP hidden / vocab / KV-head "
                         "axis) — token-identical to 1 device, per-device "
                         "bytes ~1/tp (DESIGN.md §4.12)")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU runs (JAX_PLATFORMS=cpu): force N fake host "
                         "devices (xla_force_host_platform_device_count) so "
                         "--tp runs on a CPU box")
    ap.add_argument("--chunked-prefill", type=int, default=None,
                    metavar="CHUNK",
                    help="prefill prompts at most CHUNK rows per step into "
                         "a staging row so decode keeps running mid-prefill "
                         "(DESIGN.md §4.12)")
    args = ap.parse_args()
    from repro.launch.mesh import force_host_devices
    force_host_devices(args.devices)   # CPU runs only
    if args.kv_bits is not None:
        args.paged = True

    lens = [int(x) for x in args.prompt_lens.split(",")]
    gens = [int(x) for x in args.gens.split(",")]
    if len(gens) == 1:
        gens = gens * len(lens)
    assert len(gens) == len(lens), "--gens must match --prompt-lens"

    eng, lm = build_engine(args.arch, smoke=True, quantized=args.quant,
                           compressed=args.compressed, packed=args.packed,
                           bits_init=args.bits, pruned=args.pruned,
                           sparsity=args.sparsity, max_slots=args.slots,
                           max_seq=max(p + g for p, g in zip(lens, gens)),
                           verbose=True, speculative=args.speculative,
                           draft_k=args.draft_k,
                           draft_sparsity=args.draft_sparsity,
                           draft_bits=args.draft_bits, paged=args.paged,
                           page_size=args.page_size, kv_bits=args.kv_bits,
                           tp=args.tp, prefill_chunk=args.chunked_prefill)
    prompts = synthetic_prompts(lm.cfg, lens)
    if args.hot_prompt:
        prompts = [prompts[0][:n].copy() for n in lens]
    rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.warmup()
    out = eng.run()
    for rid, n, g in zip(rids, lens, gens):
        toks = " ".join(str(t) for t in out[rid][:12])
        more = " ..." if len(out[rid]) > 12 else ""
        print(f"request {rid}: prompt {n} tokens -> {len(out[rid])}/{g} "
              f"generated: {toks}{more}")
    th = eng.throughput()
    s = eng.stats
    line = (f"decode: {s['decode_tokens']} tokens in {s['decode_s']:.2f}s "
            f"({th['decode_tok_per_s']:.1f} tok/s, occupancy "
            f"{th['slot_occupancy']:.2f} over {args.slots} slots); "
            f"one-shot prefill: {s['prefill_tokens']} tokens "
            f"({th['prefill_tok_per_s']:.1f} tok/s)")
    if args.speculative:
        line += (f"; speculative: {s['spec_accepted']}/{s['spec_drafted']} "
                 f"drafted tokens accepted "
                 f"({th['acceptance_rate']:.2f}) over {s['spec_steps']} "
                 f"rounds")
    if args.paged:
        line += (f"; paged: {s['prefills']} prefills, "
                 f"{s['prefix_hits']} prefix hits, kv_bytes "
                 f"{eng.kv_bytes()} of {eng.kv_pool_bytes()} pooled")
    if args.tp:
        line += (f"; tp={args.tp}: param bytes/device "
                 f"{eng.param_bytes(per_device=True)} of "
                 f"{eng.param_bytes()}, kv bytes/device "
                 f"{eng.kv_bytes(per_device=True)} of {eng.kv_bytes()}")
    if args.chunked_prefill:
        line += (f"; chunked@{args.chunked_prefill}: "
                 f"{s['prefill_chunks']} chunks, "
                 f"{s['decode_steps_mid_prefill']} decode steps "
                 f"mid-prefill")
    print(line)


if __name__ == "__main__":
    main()
