"""Static launch introspection + VMEM footprint models for the kernels.

The static verifier (`repro.analysis`) needs to know, for a traced engine
entry point, which Pallas launches the trace would dispatch on a TPU and
at what tile geometry — *without* running anything and *without* a TPU:
on CPU the dispatch layer routes every op to the xla-ref oracle, so the
Pallas wrappers themselves never execute. The hooks therefore live at the
dispatch layer (`ops.decode_attn_op`, `gemm_core.gemm`), *after* block
resolution but *before* the backend branch: every backend records the
tile the compiled-TPU path would use.

Recording is off by default and costs one `is None` check per traced op.
`record_launches()` turns it on for the duration of a trace:

    with introspect.record_launches() as launches:
        jax.make_jaxpr(engine._decode)(params, qparams, caches, tok, pos)
    # launches: [GemmLaunch(...), AttnLaunch(...), ...]

The VMEM byte models below are deliberately simple upper-estimate
arithmetic over the block specs (2x double-buffering on grid-streamed
blocks, accumulator + scratch resident, one decoded field plane of a
packed word tile at a time) against the ~16 MiB/core budget from the
Pallas TPU guide. They are used by the analysis `vmem` pass *and* by
`autotune.autotune_gemm` to refuse timing candidate tiles that could not
fit on real hardware.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

# Per-core VMEM on current TPU generations (the Pallas guide's planning
# number). The budget below leaves headroom for compiler-managed
# temporaries; tiles past it are rejected statically.
VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = VMEM_BYTES

_F32 = 4
_LANES = 128        # mirror of decode_attn._LANES (scratch minor dim)


@dataclasses.dataclass(frozen=True)
class GemmLaunch:
    """One `gemm_core.gemm` dispatch: logical shape + resolved tile."""
    M: int
    N: int
    K: int
    k_pack: int                       # codes per int32 word (1 = unpacked)
    n_col: int                        # COL (1, bn) operand count
    n_scalar: int                     # SCALAR (1, 1) operand count
    ops: str                          # autotune.ops_key epilogue identity
    backend: str
    blocks: tuple[int, int, int, int]  # (bm, bn, bk, bkw) final tile
    w_itemsize: int = 4
    x_itemsize: int = 4               # x tile (and packed plane) element

    kind = "gemm"

    def describe(self) -> str:
        bm, bn, bk, bkw = self.blocks
        return (f"gemm {self.M}x{self.N}x{self.K}|{self.ops} "
                f"tile bm={bm} bn={bn} bk={bk} bkw={bkw}")


@dataclasses.dataclass(frozen=True)
class AttnLaunch:
    """One flash-decode attention dispatch (contiguous or paged). Each
    grid step takes every KV head: q/out blocks (KVh, gp, dh), K/V blocks
    (chunk, KVh * dhs) — see `kernels.decode_attn`."""
    kind: str                         # "decode_attn" | "paged_decode_attn"
    B: int
    KVh: int
    g: int                            # query heads per KV head
    dh: int
    gp: int                           # query-group rows padded to 8
    dhs: int                          # stored K/V width per head (dh, or
    #                                   dh // 2 nibble pairs at kv_bits=4)
    chunk: int                        # K rows per grid step (page_size
    #                                   for the paged kernel)
    backend: str = "xla-ref"          # what this dispatch resolved to
    kv_itemsize: int = 4              # pool element bytes (1 for codes)
    scaled: bool = False              # per-row scale blocks ride along

    def describe(self) -> str:
        return (f"{self.kind} B={self.B} KVh={self.KVh} g={self.g} "
                f"dh={self.dh} tile gp={self.gp} dhs={self.dhs} "
                f"chunk={self.chunk}")


_records: Optional[list] = None


@contextlib.contextmanager
def record_launches():
    """Collect every kernel-dispatch note issued while tracing inside the
    block. Reentrant use shares the innermost list (the analysis registry
    traces one entry at a time)."""
    global _records
    prev = _records
    _records = [] if prev is None else prev
    try:
        yield _records
    finally:
        _records = prev


def recording() -> bool:
    return _records is not None


def note(launch) -> None:
    if _records is not None:
        _records.append(launch)


# ------------------------------------------------------- VMEM byte models
def gemm_vmem_bytes(launch: GemmLaunch) -> int:
    """Estimated VMEM bytes for one gemm tile-program.

    2x double-buffering on the streamed input blocks (x, w, COL/SCALAR
    operands) and 2x an f32 (bm, bn) output tile (the unpacked body's
    accumulator, whose (i, j) revisit overlaps the next block's
    prologue; the packed body's output block and its f32 K-step
    scratch). The packed body (`k_pack > 1`) streams x as cpw field
    slices, bm x bk elements of x's width, and the (bkw, bn) int32 word
    tile, and decodes one field plane at a time: an int32 plane and its
    copy in the MXU operand dtype (x's: bf16 planes for bf16 x). No
    (bk, bn) decoded tile is ever formed."""
    bm, bn, bk, bkw = launch.blocks
    x_tile = bm * bk * (launch.x_itemsize if launch.k_pack > 1 else _F32)
    w_tile = bkw * bn * launch.w_itemsize
    operands = launch.n_col * bn * _F32 + launch.n_scalar * _F32
    out_tile = bm * bn * _F32
    plane = (bkw * bn * (_F32 + launch.x_itemsize)
             if launch.k_pack > 1 else 0)
    return 2 * (x_tile + w_tile + operands) + 2 * out_tile + plane


def attn_vmem_bytes(launch: AttnLaunch) -> int:
    """Estimated VMEM bytes for one flash-decode tile-program: the
    all-heads q and out blocks, double-buffered K/V chunk blocks (+ per-row
    scales when the pool holds codes), the per-head running max/denom
    scratch, and — one head at a time — the f32 (chunk, dh) K/V tiles the
    kernel decodes and the (gp, chunk) probability tile."""
    heads = launch.KVh
    q_tile = heads * launch.gp * launch.dh * _F32
    kv = 2 * launch.chunk * heads * launch.dhs * launch.kv_itemsize
    scales = 2 * launch.chunk * heads * _F32 if launch.scaled else 0
    out_tile = heads * launch.gp * launch.dh * _F32
    scratch = 2 * heads * launch.gp * _LANES * _F32
    per_head = (2 * launch.chunk * launch.dh + launch.gp * launch.chunk) * _F32
    return 2 * (q_tile + kv + scales) + 2 * out_tile + scratch + per_head


def launch_vmem_bytes(launch) -> int:
    if isinstance(launch, GemmLaunch):
        return gemm_vmem_bytes(launch)
    if isinstance(launch, AttnLaunch):
        return attn_vmem_bytes(launch)
    raise TypeError(f"not a launch record: {launch!r}")


def over_budget(launch, budget: Optional[int] = None) -> bool:
    return launch_vmem_bytes(launch) > (budget or VMEM_BUDGET_BYTES)
