"""The static contract passes (DESIGN.md §4.13).

1. `audit_identity`    — no cross-device reduction primitive in any TP
   serving jaxpr; training reductions only as ordered all_gathers inside
   the `make_ordered_loss_grads` shard_map. Optional second layer scans
   the *compiled* HLO for float add-combiner all-reduces GSPMD might
   introduce after SPMD partitioning (trace-level absence is necessary,
   not sufficient).
2. `audit_sharding_pins` — every arena/row-returning jit declares
   out_shardings matching the `kv_cache_specs`-derived contract (flags
   the operand-propagation pattern the pre-PR-10 `_insert` relied on).
3. `audit_compile_set` — brute-force the reachable dispatch-shape sets
   (decode windows, spec ks, chunk shapes) independently of the engine's
   warmup code and fail if warmup's precompiled set doesn't cover them.
4. VMEM budget — see `analysis.vmem`.
5. `audit_constants` — large closure-captured constants (silent HBM
   pinning + retrace hazards) and f64-widening `convert_element_type`.

Each pass maps traced entries (or live engines, for the compile-set
audit) to `report.Finding`s with stable IDs.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from repro.analysis import jaxpr_utils as ju
from repro.analysis.report import Finding, make_finding

# Cross-device *reduction* primitives: combining values from different
# devices, where combine order can reassociate float sums — banned
# everywhere in serving (TP is column/head-parallel by construction: no
# contraction ever splits) and allowed in training only via the
# slice-ordered path below.
REDUCTION_PRIMS = frozenset({
    "psum", "psum_invariant", "unreduced_psum", "reduce_scatter",
    "unreduced_reduce_scatter", "all_to_all", "ragged_all_to_all",
    "pmax", "pmin",
})
# Pure data movement: bitwise replication/rotation, no arithmetic.
# Banned in serving jaxprs too (nothing should move between devices
# mid-decode), but allowed inside the trainer's shard_map (the ordered
# reduction gathers slices and sums them in a fixed order locally).
MOVEMENT_PRIMS = frozenset({"all_gather", "all_gather_invariant",
                            "all_gather_reduced", "ppermute", "pbroadcast"})

IDENTITY = "identity"
SHARDING = "sharding"
COMPILE_SET = "compile_set"
CONSTANTS = "constants"


# ------------------------------------------------------ 1: identity audit
def audit_identity(traced_entries, compiled: bool = False
                   ) -> list[Finding]:
    findings = []
    for te in traced_entries:
        hits = ju.find_prims(te.jaxpr, REDUCTION_PRIMS | MOVEMENT_PRIMS)
        counted: dict[tuple, int] = {}
        for eqn, path in hits:
            prim = eqn.primitive.name
            if te.kind == "training":
                # the deterministic trainer's only legal collective: an
                # all_gather inside the make_ordered_loss_grads shard_map
                # (gather slices, sum in fixed order locally)
                if prim in MOVEMENT_PRIMS and ju.in_shard_map(path):
                    continue
            counted[(prim, ju.in_shard_map(path))] = \
                counted.get((prim, ju.in_shard_map(path)), 0) + 1
        for (prim, inside_sm), n in sorted(counted.items()):
            where = "inside shard_map" if inside_sm else "at top level"
            if te.kind == "training":
                msg = (f"training jaxpr contains {n}x `{prim}` {where} — "
                       f"reductions must flow through the slice-ordered "
                       f"all_gather+local-sum path only")
            else:
                msg = (f"TP serving jaxpr contains {n}x `{prim}` {where} — "
                       f"serving must stay collective-free (token identity "
                       f"holds because no contraction ever splits)")
            findings.append(make_finding(
                IDENTITY, te.group, te.name, prim, msg,
                detail={"count": n, "in_shard_map": inside_sm}))
        if compiled and te.tp > 1 and te.kind == "serving":
            findings.extend(_compiled_identity(te))
    return findings


def _hlo_computations(text: str) -> dict[str, str]:
    """name -> body for every computation in an HLO text dump."""
    comps: dict[str, str] = {}
    name, body = None, []
    for line in text.splitlines():
        m = re.match(r"\s*(ENTRY\s+)?(%?[\w.\-]+)\s*(\([^)]*\))?.*\{\s*$",
                     line)
        if m and name is None:
            name = m.group(2).lstrip("%")
            body = []
            continue
        if name is not None:
            if line.strip() == "}":
                comps[name] = "\n".join(body)
                name = None
            else:
                body.append(line)
    return comps


# HLO all-reduces whose JAX source op is a masked one-hot assembly:
# every output element has exactly one nonzero contributor (a sharded
# embedding gather, a KV-cache concatenate/update assembled from
# per-device shards), so the add combiner sums x+0+...+0 — bitwise
# exact, no reassociation. Everything else (dot_general above all:
# GSPMD's K-split partial-dot + all-reduce rewrite) genuinely
# reassociates a float sum and is flagged.
_EXACT_ASSEMBLY_OPS = frozenset({
    "gather", "concatenate", "dynamic_update_slice", "dynamic-update-slice",
    "scatter", "select_n",
})

_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*(\S+)\s+(all-reduce|reduce-scatter)\(.*?to_apply=(%?[\w.\-]+)")
_HLO_OPNAME_RE = re.compile(r'op_name="[^"]*?/([\w\-]+)"')


def _compiled_identity(te) -> list[Finding]:
    """Scan the post-SPMD compiled HLO: flag reduce-scatter always and
    all-reduce when its combiner is a float add on a genuinely
    multi-contributor sum (reassociation hazard). Max/min combiners are
    exact (sharded-vocab argmax), all-gather is bitwise movement, and
    one-hot-assembly adds (see `_EXACT_ASSEMBLY_OPS`) are exact."""
    findings = []
    try:
        text = te.fn.lower(*te.args).compile().as_text()
    except Exception as exc:    # lowering is best-effort hardening
        findings.append(make_finding(
            IDENTITY, te.group, te.name, "hlo-lower-failed",
            f"could not lower/compile for the HLO identity scan: {exc}",
            severity="warning"))
        return findings
    comps = _hlo_computations(text)
    flagged = set()
    for line in text.splitlines():
        m = _HLO_COLLECTIVE_RE.search(line)
        if not m:
            continue
        rtype, op, region = m.groups()
        region = region.lstrip("%")
        src = _HLO_OPNAME_RE.search(line)
        src_op = src.group(1) if src else "unknown"
        body = comps.get(region, "")
        is_float = bool(re.match(r"\(?(f16|f32|f64|bf16)", rtype))
        is_add = re.search(r"\badd\(", body) is not None
        if op == "all-reduce" and not (is_float and is_add):
            continue
        if op == "all-reduce" and src_op in _EXACT_ASSEMBLY_OPS:
            continue
        slug = f"hlo-{op}-{src_op}"
        if slug in flagged:
            continue
        flagged.add(slug)
        findings.append(make_finding(
            IDENTITY, te.group, te.name, slug,
            f"compiled HLO contains `{op}` with a float add combiner "
            f"over a `{src_op}` (region {region}) — the SPMD partitioner "
            f"introduced a cross-device reduction the trace-level audit "
            f"cannot see",
            detail={"result_type": rtype, "region": region,
                    "source_op": src_op}))
    return findings


# -------------------------------------------------- 2: sharding-pin audit
def audit_sharding_pins(traced_entries) -> list[Finding]:
    findings = []
    for te in traced_entries:
        if te.expected_out is None or te.kind != "serving":
            continue
        import jax
        jit_eqn = ju.outer_jit_eqn(te.jaxpr)
        if jit_eqn is None:
            findings.append(make_finding(
                SHARDING, te.group, te.name, "no-jit",
                "entry did not trace to a single jit equation — cannot "
                "audit its out_shardings", severity="warning"))
            continue
        actual = ju.out_shardings_of(jit_eqn)
        leaves_p = jax.tree_util.tree_flatten_with_path(te.expected_out)[0]
        if len(actual) != len(leaves_p):
            findings.append(make_finding(
                SHARDING, te.group, te.name, "arity",
                f"out_shardings arity {len(actual)} != expected "
                f"{len(leaves_p)} leaves — contract tree is stale"))
            continue
        for (path, want), got in zip(leaves_p, actual):
            leaf = jax.tree_util.keystr(path) or "out"
            slug = re.sub(r"[^A-Za-z0-9_.\[\]]+", "", leaf) or "out"
            if ju.is_unspecified(got):
                findings.append(make_finding(
                    SHARDING, te.group, te.name, f"unpinned{slug}",
                    f"output leaf {leaf} has no out_sharding pinned — the "
                    f"arena's placement would be operand-propagated "
                    f"instead of contractual"))
            elif ju.spec_of(got) != ju.spec_of(want):
                findings.append(make_finding(
                    SHARDING, te.group, te.name, f"mismatch{slug}",
                    f"output leaf {leaf} pins {ju.spec_of(got)} but the "
                    f"kv_cache_specs contract says {ju.spec_of(want)}"))
    return findings


# --------------------------------------------------- 3: compile-set audit
def audit_compile_set(engines: dict) -> list[Finding]:
    """Diff brute-forced reachable dispatch-shape sets against the
    warmup contract, per engine config. Reachable sets are enumerated
    from the *dispatch-site quantizers* (`pow2_floor`, `chunk_plan`),
    warmed sets from the engine's own warmup helpers — independent
    derivations, so a shared bug can't hide."""
    from repro.launch.scheduler import chunk_buckets, reachable_chunk_shapes
    from repro.launch.speculative import pow2_floor, reachable_spec_ks

    findings = []
    for group, eng in sorted(engines.items()):
        if eng.draft is not None:
            reach = reachable_spec_ks(eng.draft_k, eng.max_seq)
            warmed = set(eng._spec_ks())
            for k in sorted(reach - warmed):
                findings.append(make_finding(
                    COMPILE_SET, group, "spec", f"k{k}",
                    f"speculative step can dispatch k={k} but warmup only "
                    f"precompiles {sorted(warmed)} — first hit would "
                    f"compile mid-serve",
                    detail={"reachable": sorted(reach),
                            "warmed": sorted(warmed)}))
        elif not eng._chunk:
            reach = {min(pow2_floor(r), eng.MAX_WINDOW)
                     for r in range(1, eng.max_seq + 1)}
            warmed = set(eng.warmed_window_ks())
            for k in sorted(reach - warmed):
                findings.append(make_finding(
                    COMPILE_SET, group, "decode_window", f"k{k}",
                    f"fused decode window can dispatch k={k} but warmup "
                    f"only precompiles {sorted(warmed)}",
                    detail={"reachable": sorted(reach),
                            "warmed": sorted(warmed)}))
        if eng._chunk:
            reach = reachable_chunk_shapes(eng.max_seq, eng._chunk)
            warmed = set(chunk_buckets(eng._chunk))
            for c in sorted(reach - warmed):
                findings.append(make_finding(
                    COMPILE_SET, group, "prefill_chunk", f"c{c}",
                    f"chunk plan can emit a length-{c} chunk but warmup "
                    f"only precompiles buckets {sorted(warmed)}",
                    detail={"reachable": sorted(reach),
                            "warmed": sorted(warmed)}))
    return findings


# --------------------------------------- 5: constant-capture / dtype audit
def audit_constants(traced_entries, max_elems: int = 1 << 16
                    ) -> list[Finding]:
    findings = []
    for te in traced_entries:
        big = ju.collect_consts(te.jaxpr, min_elems=max_elems + 1)
        seen: dict[str, int] = {}
        for path, c in big:
            shape = tuple(np.shape(c))
            dtype = np.asarray(c).dtype if not hasattr(c, "dtype") \
                else c.dtype
            slug = "x".join(map(str, shape)) + f"-{dtype}"
            seen[slug] = seen.get(slug, 0) + 1
            if seen[slug] > 1:
                continue    # one finding per distinct shape/dtype
            nbytes = int(np.size(c)) * np.dtype(dtype).itemsize
            findings.append(make_finding(
                CONSTANTS, te.group, te.name, f"const-{slug}",
                f"trace closure-captured a {shape} {dtype} constant "
                f"(~{nbytes / 2**20:.1f} MiB) — it pins HBM outside the "
                f"param tree and retraces on every new closure",
                detail={"shape": list(shape), "dtype": str(dtype),
                        "path": list(path)}))
        for eqn, _ in ju.walk_eqns(te.jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            new = eqn.params.get("new_dtype")
            if new is not None and np.dtype(new) == np.dtype(np.float64):
                findings.append(make_finding(
                    CONSTANTS, te.group, te.name, "f64-widen",
                    "jaxpr widens to float64 — serving/training math is "
                    "f32; an f64 convert doubles bytes and falls off the "
                    "MXU path"))
                break
    return findings


def run_all(engines: dict, traced_entries, *, compiled: bool = False,
            vmem_budget: Optional[int] = None,
            const_max_elems: int = 1 << 16) -> list[Finding]:
    from repro.analysis.vmem import audit_vmem
    findings = []
    findings += audit_identity(traced_entries, compiled=compiled)
    findings += audit_sharding_pins(traced_entries)
    findings += audit_compile_set(engines)
    findings += audit_vmem(traced_entries, budget=vmem_budget)
    findings += audit_constants(traced_entries, max_elems=const_max_elems)
    return findings
