"""(bm, bn, bk) block-size autotuner for the shared GEMM core.

`DEFAULT_BLOCKS = (128, 128, 128)` was tuned for full-width checkpoint
shapes. TP serving divides every projection's N by the mesh size and
pruning shrinks K/N further, so the hot GEMMs move to a corner of shape
space where a different tile wins (small-N shards want deeper bk; tall
packed streams want the word-aligned bk the core already forces). This
module closes that gap without touching call sites:

  * `gemm(..., blocks=None)` (the new default) consults `lookup()` — a
    per-(M, N, K, epilogue, backend) table — and falls back to
    `DEFAULT_BLOCKS` on a miss. Zero overhead beyond one dict probe per
    *trace* (the probe happens at trace time; compiled dispatches never
    see it).
  * `autotune_gemm(x, w, ops)` times the candidate tile set for one
    concrete GEMM, records the winner, and persists the table as JSON so
    a deployment tunes once and every later process starts warm.

The cache file lives at ``REPRO_GEMM_TUNE_CACHE`` (env var; unset means
in-memory only — tests and CI stay hermetic unless they opt in).

Keys are strings ``"MxNxK|op1+op2|backend"`` — N is the *local* width, so
a TP shard and the full-width GEMM tune independently, which is the whole
point. Only the compiled Pallas backends are worth tuning; `xla-ref`
ignores blocks and `autotune_gemm` refuses it.
"""
from __future__ import annotations

import json
import os
import time
from typing import Optional, Sequence

import jax
import numpy as np

ENV_VAR = "REPRO_GEMM_TUNE_CACHE"

# key -> (bm, bn, bk); lazily seeded from the cache file on first use.
_memory: dict[str, tuple[int, int, int]] = {}
_loaded_from: Optional[str] = None


def cache_path() -> Optional[str]:
    return os.environ.get(ENV_VAR) or None


def ops_key(rhs_ops: Sequence) -> str:
    """Epilogue identity for the cache key: op names in application order.

    Operand *values* (scales, masks) don't change the tiling economics;
    op structure (packed word streams, extra COL loads) does — and the
    names encode it (`unpack_dequant_b4` vs `dequant` vs `col_mask`)."""
    return "+".join(op.name for op in rhs_ops) or "dense"


def _key(M: int, N: int, K: int, ops: str, backend: str) -> str:
    return f"{M}x{N}x{K}|{ops}|{backend}"


def clear(*, memory_only: bool = True) -> None:
    """Drop the in-memory table (tests). The file is never deleted."""
    global _loaded_from
    _memory.clear()
    _loaded_from = None
    del memory_only


def load(path: Optional[str] = None) -> dict[str, tuple[int, int, int]]:
    """Merge the persisted table (if any) into memory and return it."""
    global _loaded_from
    path = path or cache_path()
    if path and os.path.exists(path) and _loaded_from != path:
        try:
            with open(path) as f:
                raw = json.load(f)
            for k, v in raw.get("blocks", {}).items():
                _memory.setdefault(k, tuple(int(b) for b in v))
            _loaded_from = path
        except (json.JSONDecodeError, OSError, TypeError, ValueError):
            pass    # a corrupt cache must never break serving
    return dict(_memory)


def save(path: Optional[str] = None) -> Optional[str]:
    path = path or cache_path()
    if not path:
        return None
    payload = {"format": "repro-gemm-tune-v1",
               "blocks": {k: list(v) for k, v in sorted(_memory.items())}}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def lookup(M: int, N: int, K: int, ops: str, backend: str
           ) -> Optional[tuple[int, int, int]]:
    if cache_path() and _loaded_from != cache_path():
        load()
    return _memory.get(_key(M, N, K, ops, backend))


def record(M: int, N: int, K: int, ops: str, backend: str,
           blocks: tuple[int, int, int], *, persist: bool = True
           ) -> None:
    _memory[_key(M, N, K, ops, backend)] = tuple(int(b) for b in blocks)
    if persist:
        save()


def candidate_blocks(M: int, N: int, K: int
                     ) -> list[tuple[int, int, int]]:
    """The tile grid worth timing for an (M, N, K) problem.

    Runs every candidate through `gemm_core._clamp_blocks` first and
    dedups, so a 4×128×256 decode GEMM times ~3 configs, not 36 — the
    clamp collapses everything the shape can't distinguish."""
    from repro.kernels import gemm_core
    out, seen = [], set()
    for bm in (32, 64, 128, 256):
        for bn in (128, 256, 512):
            for bk in (128, 256, 512):
                b = gemm_core._clamp_blocks((bm, bn, bk), M, N, K)
                if b not in seen:
                    seen.add(b)
                    out.append(b)
    return out


def vmem_filter(candidates, M: int, N: int, K: int, rhs_ops=(), *,
                w_itemsize: int = 4, x_itemsize: int = 4,
                budget: Optional[int] = None):
    """Split candidate (bm, bn, bk) tiles by the static VMEM model.

    Each candidate resolves through `gemm_core.plan_blocks` — the exact
    tile `gemm` would launch — and its footprint is estimated by
    `introspect.gemm_vmem_bytes`. Returns (fits, rejected) where
    `rejected` maps the candidate to its estimated bytes; `budget`
    defaults to `introspect.VMEM_BUDGET_BYTES` (~16 MiB/core)."""
    from repro.kernels import gemm_core, introspect
    k_pack = rhs_ops[0].k_pack if rhs_ops else 1
    n_col = sum(kk == gemm_core.COL for op in rhs_ops for kk in op.kinds)
    n_scalar = sum(kk == gemm_core.SCALAR
                   for op in rhs_ops for kk in op.kinds)
    budget = budget or introspect.VMEM_BUDGET_BYTES
    fits, rejected = [], {}
    for blocks in candidates:
        plan = gemm_core.plan_blocks(M, N, K, k_pack, tuple(blocks),
                                     x_itemsize=x_itemsize)
        nbytes = introspect.gemm_vmem_bytes(introspect.GemmLaunch(
            M=M, N=N, K=K, k_pack=k_pack, n_col=n_col, n_scalar=n_scalar,
            ops=ops_key(rhs_ops), backend="static", blocks=plan,
            w_itemsize=w_itemsize, x_itemsize=x_itemsize))
        if nbytes > budget:
            rejected[tuple(blocks)] = nbytes
        else:
            fits.append(tuple(blocks))
    return fits, rejected


def autotune_gemm(x, w, rhs_ops=(), *, backend: Optional[str] = None,
                  candidates=None, repeats: int = 3, out_dtype=None,
                  persist: bool = True, vmem_budget: Optional[int] = None):
    """Time `gemm` over the candidate tiles, record + return the winner.

    Returns (best_blocks, {blocks: seconds}). Each candidate is compiled
    once (untimed) then timed best-of-`repeats` with blocked dispatches.
    The winner lands in the in-memory table immediately — the very next
    `gemm(..., blocks=None)` trace of this shape picks it up — and in the
    cache file when ``REPRO_GEMM_TUNE_CACHE`` is set and `persist`.

    Candidates whose static VMEM footprint exceeds `vmem_budget`
    (default: the ~16 MiB/core TPU budget) are dropped *before* timing —
    a tile that would OOM real VMEM must not win a CPU-interpret race."""
    from repro.kernels import dispatch, gemm_core
    backend = dispatch.resolve(backend)
    if backend == "xla-ref":
        raise ValueError("autotune_gemm tunes the Pallas tiling; xla-ref "
                         "ignores blocks — nothing to tune")
    M, K = x.shape
    k_pack = rhs_ops[0].k_pack if rhs_ops else 1
    N = w.shape[1]
    K_logical = K if k_pack == 1 else K    # x carries logical K already
    cands = list(candidates or candidate_blocks(M, N, K_logical))
    x_itemsize = gemm_core.x_itemsize(x, k_pack)
    cands, rejected = vmem_filter(cands, M, N, K_logical, rhs_ops,
                                  w_itemsize=w.dtype.itemsize,
                                  x_itemsize=x_itemsize,
                                  budget=vmem_budget)
    if not cands:
        raise ValueError(
            f"every candidate tile exceeds the VMEM budget "
            f"({ {k: v for k, v in sorted(rejected.items())} })")
    timings: dict[tuple[int, int, int], float] = {}
    for blocks in cands:
        fn = jax.jit(lambda a, b: gemm_core.gemm(
            a, b, tuple(rhs_ops), blocks=blocks, backend=backend,
            out_dtype=out_dtype))
        jax.block_until_ready(fn(x, w))           # compile, untimed
        best = np.inf
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, w))
            best = min(best, time.perf_counter() - t0)
        timings[blocks] = best
    winner = min(timings, key=timings.get)
    record(M, N, K_logical, ops_key(rhs_ops), backend, winner,
           persist=persist)
    return winner, timings
