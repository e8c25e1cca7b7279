"""Single tiled-GEMM core with composable RHS-transform epilogues.

Every matmul-shaped kernel in this package — structured-mask matmul
(training joint stage), int-code dequant matmul (compressed serving), and
the fused fake-quant + mask projection — is the *same* (bm, bn, bk)
MXU-aligned pipeline differing only in how the weight tile is transformed
after the HBM->VMEM load. This module owns that pipeline once:

  y = x @ T(w),    T = op_n ∘ ... ∘ op_1      (applied to RHS tiles in VMEM)

with pad-to-block / slice-back handled in exactly one place. The legacy
entry points (`masked_matmul.py`, `quant_matmul.py`) are thin op-configs
over `gemm()`.

Blocking: classic (bm, bn, bk) tiling with f32 accumulation into the output
block across the K grid axis. K is the innermost / fastest-varying grid
dimension, so revisits of an (i, j) output block are consecutive and the
accumulator pattern is valid on TPU.

Each `RhsOp` declares its operands as either a per-output-column vector
("col", shape (N,), delivered as a (1, bn) VMEM block riding the j grid
axis) or a scalar ("scalar", delivered as a (1, 1) block mapped to every
grid step). `op.apply` consumes jnp values, so the same callable serves the
Pallas kernel body *and* the xla-ref oracle backend. A K-packed word
stream (`unpack_dequant`) is the exception: it runs a body of its own
(`_packed_kernel`, DESIGN.md §4.8), and its `apply` serves the oracle.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import _EPS, clip_qmt, code_plane, unpack_codes
from repro.kernels import autotune, dispatch, introspect

DEFAULT_BLOCKS = (128, 128, 128)  # bm, bn, bk

COL, SCALAR = "col", "scalar"


@dataclasses.dataclass(frozen=True)
class RhsOp:
    """One composable transform of the (bk, bn) RHS tile.

    kinds: operand kinds, each COL ((N,) vector, blocked (1, bn)) or
           SCALAR ((1, 1) everywhere).
    apply: (w_f32, *operand_values) -> w_f32; operand values arrive as
           (1, bn) / (1, 1) f32 arrays (full-width (1, N) on xla-ref).
    bits:  >0 marks a bit-unpacking op: the RHS array is stored packed
           along K, `k_pack` = 32 // bits codes per int32 word of
           `bits`-wide fields. Only the first op may unpack, and only COL
           ops may follow it. Such a GEMM runs the packed body
           (`_packed_kernel`), which decodes field planes itself and
           multiplies the op's COL operand and every later op into the
           result; `apply` (raw word tile -> decoded f32 (K, N) tile)
           serves the xla-ref oracle alone.
    """
    name: str
    kinds: tuple[str, ...]
    apply: Callable[..., jax.Array]
    operands: tuple[jax.Array, ...]
    bits: int = 0

    def __post_init__(self):
        assert len(self.kinds) == len(self.operands), (self.name, self.kinds)

    @property
    def k_pack(self) -> int:
        return 32 // self.bits if self.bits else 1


# ------------------------------------------------------------- op factories
def col_mask(mask: jax.Array) -> RhsOp:
    """w *= mask[None, :] — structured column (pruning-group) mask."""
    return RhsOp("col_mask", (COL,), lambda w, m: w * m, (mask,))


def dequant(scale: jax.Array) -> RhsOp:
    """w = codes * scale[None, :] — int-code dequantization."""
    return RhsOp("dequant", (COL,), lambda w, s: w * s, (scale,))


def unpack_dequant(bits: int, scale: jax.Array) -> RhsOp:
    """Sub-byte decode: int32 K-packed words -> codes, times scale.

    The RHS streams HBM->VMEM as `core.quant.pack_codes` words (32//bits
    codes per word, LSB field first, packed along K) and is decoded
    inside VMEM one field plane at a time (`_packed_kernel`), so a 4-bit
    site moves half the HBM bytes of its int8 container. The scale, and
    later COL ops (`col_mask`), multiply the result columns after the
    dot: x @ (c * s) == (x @ c) * s."""
    bits = int(bits)
    if not 2 <= bits <= 8:
        raise ValueError(f"unpack_dequant bits must be in [2, 8]: {bits}")
    cpw = 32 // bits

    def apply(words, s):
        # words: (Wk, n) int32 — the raw packed stream (the xla-ref path
        # hands it over uncast); returns the decoded (Wk * cpw, n) f32
        # codes times the scale. The format is defined in `core.quant`
        # alone: this oracle decodes with `unpack_codes`, the kernel with
        # `code_plane`, and a test pins the two equal.
        codes = unpack_codes(words, bits, words.shape[0] * cpw, axis=0)
        return codes.astype(jnp.float32) * s

    return RhsOp(f"unpack_dequant_b{bits}", (COL,), apply, (scale,),
                 bits=bits)


def _fq_apply(w, dv, qmv, tv):
    # Reuses core.quant.clip_qmt so the in-tile rounding decisions match
    # the XLA quantizer bit-for-bit (a reimplementation that differs by
    # 1 ulp flips round ties by a whole step of d).
    d = jnp.maximum(dv[0, 0], _EPS)
    xt = clip_qmt(jnp.abs(w), qmv[0, 0], tv[0, 0])
    return d * jnp.round(xt / d) * jnp.sign(w)


def fake_quant_rhs(d: jax.Array, q_m: jax.Array, t: jax.Array) -> RhsOp:
    """w = fake_quant(w; d, q_m, t) — paper Eqs (1)-(2) on the weight tile."""
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(())
    return RhsOp("fake_quant", (SCALAR,) * 3, _fq_apply,
                 (scal(d), scal(q_m), scal(t)))


def fq_mask_ops(d, q_m, t, mask) -> tuple[RhsOp, ...]:
    """The GETA joint-stage RHS: fake_quant(w) * mask in one HBM pass."""
    return (fake_quant_rhs(d, q_m, t), col_mask(mask))


# ----------------------------------------------------------------- kernel
def _make_kernel(ops: tuple[RhsOp, ...]):
    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        op_refs = refs[2:-1]
        o_ref = refs[-1]
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        x = x_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        i = 0
        for op in ops:
            vals = [op_refs[i + j][...].astype(jnp.float32)
                    for j in range(len(op.kinds))]
            w = op.apply(w, *vals)
            i += len(op.kinds)
        o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    return kernel


def _packed_kernel(bits: int, n_k: int, n_col: int):
    """The packed-RHS body: y = sum_i x_i @ plane_i, then * COL operands.

    x arrives in field-plane order, (cpw, bm, bkw): x_ref[i] holds the x
    columns of the K rows that field i of the (bkw, bn) word tile codes.
    Each plane is decoded by two shifts (`core.quant.code_plane`) and cast
    to x's dtype: codes of at most 8 bits are exact in bf16, and the MXU
    accumulates in f32. No (bk, bn) tile is formed and nothing is
    interleaved. The scale (and any later COL op) multiplies the f32
    (bm, bn) result once, at the last K step; across K steps the sum
    rides an f32 scratch."""
    def kernel(x_ref, w_ref, *refs):
        col_refs, o_ref, acc_refs = refs[:n_col], refs[n_col], \
            refs[n_col + 1:]
        w = w_ref[...]
        acc = None
        for i in range(32 // bits):
            plane = code_plane(w, bits, i).astype(jnp.float32)
            part = jnp.dot(x_ref[i], plane.astype(x_ref.dtype),
                           preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part

        def finish(y):
            for c_ref in col_refs:
                y = y * c_ref[...]
            o_ref[...] = y.astype(o_ref.dtype)

        if n_k == 1:
            finish(acc)
            return
        (acc_ref,) = acc_refs
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _first():
            acc_ref[...] = acc

        @pl.when(k > 0)
        def _more():
            acc_ref[...] += acc

        @pl.when(k == n_k - 1)
        def _last():
            finish(acc_ref[...])

    return kernel


def _clamp_blocks(blocks, M, N, K):
    """Shrink the (bm, bn, bk) tile to cover small inputs without waste.

    bm clamps to the *8-sublane-aligned* cover of M: decode GEMMs run at
    M = active slots (often 4-8), and an M=4 input under the default
    bm=128 would pad 97% of the tile; rounding M up to a multiple of 8
    keeps the tile MXU-legal (f32 min sublane tile is 8) while the pad
    stays < 8 rows. bn/bk floor at the 128-lane tile."""
    bm, bn, bk = blocks
    return (min(bm, -(-M // 8) * 8), min(bn, max(128, N)),
            min(bk, max(128, K)))


# The packed plan by M (DESIGN.md §4.8). A decode GEMM (M <= DECODE_ROWS)
# is a weight stream: each grid step should move STEP_WORD_BYTES of words
# (a step costs a fixed ~0.2-0.35 us, which an 8 KiB word tile cannot
# hide) over MIN_STEPS or more steps, so the next tile's DMA overlaps the
# decode. A prefill GEMM is MXU work: row blocks of up to PREFILL_BM,
# PREFILL_BN-wide columns and K whole, so each plane is decoded for few
# row blocks and the x block stays resident across the columns.
DECODE_ROWS = 64
STEP_WORD_BYTES = (256 << 10, 1 << 20)    # floor, cap
MIN_STEPS = 4
PREFILL_BM, PREFILL_BN = 512, 512
K_WORDS = 1024          # words of K one block holds at most
# Plans aim below the VMEM budget: Mosaic keeps its own temporaries too.
PLAN_VMEM_BYTES = 3 * introspect.VMEM_BUDGET_BYTES // 4


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _lane_divisors(n: int, cap: int) -> list[int]:
    """Multiples of 128 that divide `n` (itself a multiple of 128), <= cap."""
    return [d for d in range(128, min(n, cap) + 1, 128) if n % d == 0]


def _k_block_words(kw: int, want: int) -> int:
    """Words of one K block: all `kw` when they fit `want`, else the
    largest 128-multiple (x's block is lane-tiled) that divides the
    128-padded stream and is at most `want`."""
    if kw <= want:
        return kw
    split = _lane_divisors(_round_up(kw, 128), max(128, want))[-1]
    return kw if split >= kw else split


def _packed_plan(M, N, K, cpw, blocks, x_itemsize):
    # whole words per K block and an MXU-legal x block: bk a multiple of
    # lcm(cpw * 8, 128) (640 for the bits=3 10-codes stream)
    unit = math.lcm(cpw * 8, 128) // cpw
    kw = _round_up(-(-K // cpw), unit)
    if blocks is not None:              # an explicit or autotuned tile
        bm, bn, bk = _clamp_blocks(blocks, M, N, K)
        bkw = _k_block_words(kw, _round_up(max(bk // cpw, 1), unit))
        return bm, bn, bkw * cpw, bkw
    n_pad = _round_up(N, 128)
    if M <= DECODE_ROWS:
        bm = _round_up(M, 8)
        bkw = _k_block_words(kw, K_WORDS)
        k_steps = _round_up(kw, bkw) // bkw
        floor, cap = STEP_WORD_BYTES
        fits = [d for d in _lane_divisors(n_pad, 2048)
                if 4 * bkw * d <= cap] or [128]
        wide = [d for d in fits if 4 * bkw * d >= floor]
        many = [d for d in wide if k_steps * n_pad // d >= MIN_STEPS]
        # the widest tile that leaves MIN_STEPS steps; else as many steps
        # as the floor allows; else (a small weight) one tile for it all
        bn = many[-1] if many else (wide[0] if wide else fits[-1])
        return bm, bn, bkw * cpw, bkw
    bn = _lane_divisors(n_pad, PREFILL_BN)[-1]
    # row blocks of equal height, so a prompt just over a block pads a
    # few rows, not a whole block (671 rows: 2 x 336, not 2 x 512)
    n_m = -(-M // PREFILL_BM)
    bkw = _k_block_words(kw, K_WORDS)
    while True:
        bm = _round_up(-(-M // n_m), 16)
        launch = introspect.GemmLaunch(
            M=M, N=N, K=K, k_pack=cpw, n_col=1, n_scalar=0, ops="plan",
            backend="static", blocks=(bm, bn, bkw * cpw, bkw),
            x_itemsize=x_itemsize)
        if not introspect.over_budget(launch, PLAN_VMEM_BYTES):
            return bm, bn, bkw * cpw, bkw
        if bm > 128:
            n_m *= 2
        elif bkw > 128:
            bkw = _k_block_words(kw, bkw // 2)
        else:
            return bm, bn, bkw * cpw, bkw


def plan_blocks(M: int, N: int, K: int, k_pack: int = 1, blocks=None, *,
                x_itemsize: int = 4) -> tuple[int, int, int, int]:
    """Resolve the final (bm, bn, bk, bkw) tile `gemm` would launch for an
    (M, N, K) problem. Shared by `gemm` and the static VMEM model
    (`kernels.introspect`) so the footprint the analyzer budgets is the
    tile the kernel actually dispatches.

    Unpacked (`k_pack == 1`): `blocks` (default `DEFAULT_BLOCKS`) under
    the clamp rule above. Packed: `blocks` if given (clamped, with bk
    rounded to whole words), else the plan by M above. Either way bk is
    a multiple of lcm(k_pack*8, 128) (128 for bits 2/4/8, 640 for the
    bits=3 10-codes stream), and bkw = bk / k_pack words either cover the
    whole (padded) word stream or are a multiple of 128 (x's field-plane
    block is lane-tiled along them). `x_itemsize` picks the packed plane
    dtype the plan budgets (bf16 x, bf16 planes)."""
    if k_pack > 1:
        return _packed_plan(M, N, K, k_pack, blocks, x_itemsize)
    bm, bn, bk = _clamp_blocks(blocks or DEFAULT_BLOCKS, M, N, K)
    return bm, bn, bk, bk


def _packed_dtype(x: jax.Array):
    """MXU operand dtype of the packed body: bf16 where x is bf16 (the
    codes are exact in it), else f32."""
    return jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32


def x_itemsize(x: jax.Array, k_pack: int) -> int:
    """Bytes of one x element as the kernel streams it (the packed body's
    x, and its planes, are in `_packed_dtype`)."""
    return jnp.dtype(_packed_dtype(x) if k_pack > 1 else x.dtype).itemsize


def gemm(x: jax.Array, w: jax.Array, rhs_ops: tuple[RhsOp, ...] = (), *,
         blocks=None, backend: str | None = None,
         out_dtype=None) -> jax.Array:
    """y = x @ T(w) with T the composition of `rhs_ops`.

    x: (M, K); w: (K, N) (any dtype castable to f32, incl. int8/int16
    codes) — or, when the first op carries `k_pack > 1` (`unpack_dequant`),
    the K-packed int32 word stream of shape (ceil(K / k_pack), N). COL
    operands are (N,) vectors; SCALAR operands are scalars. Pads every dim
    to block multiples once; output sliced back to (M, N).

    `blocks=None` (default) consults the `kernels.autotune` per-shape
    table — a tuned (bm, bn, bk) for this exact (M, N, K, epilogue,
    backend) if one was recorded; else `DEFAULT_BLOCKS`, or for a packed
    RHS the plan by M (`plan_blocks`) — so TP shards and pruned widths
    don't run tiles sized for full shapes. Pass explicit blocks to bypass
    the table (parity tests do).
    """
    backend = dispatch.resolve(backend)
    M, K = x.shape
    k_pack = rhs_ops[0].k_pack if rhs_ops else 1
    assert all(op.k_pack == 1 for op in rhs_ops[1:]), \
        "only the leading RHS op may unpack"
    Kw, N = w.shape
    if k_pack > 1:
        assert Kw == -(-K // k_pack), (x.shape, w.shape, k_pack)
        assert all(kind == COL for op in rhs_ops for kind in op.kinds), \
            "a packed RHS composes with COL ops only"
    else:
        assert K == Kw, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype

    if blocks is None:
        blocks = autotune.lookup(M, N, K, autotune.ops_key(rhs_ops),
                                 backend)
        if blocks is None and k_pack == 1:
            blocks = DEFAULT_BLOCKS

    x_bytes = x_itemsize(x, k_pack)
    plan = plan_blocks(M, N, K, k_pack, blocks, x_itemsize=x_bytes)
    if introspect.recording():
        # the tile the compiled-TPU path would launch, recorded even when
        # this trace routes to xla-ref (CPU CI statically audits the TPU
        # footprint — see kernels.introspect)
        introspect.note(introspect.GemmLaunch(
            M=M, N=N, K=K, k_pack=k_pack,
            n_col=sum(k == COL for op in rhs_ops for k in op.kinds),
            n_scalar=sum(k == SCALAR for op in rhs_ops for k in op.kinds),
            ops=autotune.ops_key(rhs_ops), backend=backend, blocks=plan,
            w_itemsize=w.dtype.itemsize, x_itemsize=x_bytes))

    if backend == "xla-ref":
        w32 = w if k_pack > 1 else w.astype(jnp.float32)
        for op in rhs_ops:
            vals = [v.astype(jnp.float32).reshape(
                        (1, -1) if kind == COL else (1, 1))
                    for kind, v in zip(op.kinds, op.operands)]
            w32 = op.apply(w32, *vals)
        if k_pack > 1:
            w32 = w32[:K]   # drop the zero codes of the final partial word
        y = x.astype(jnp.float32) @ w32
        return y.astype(out_dtype)

    if k_pack > 1:
        return _packed_gemm(x, w, rhs_ops, plan, backend, out_dtype)

    bm, bn, bk, _ = plan
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    operands = []
    for op in rhs_ops:
        for kind, v in zip(op.kinds, op.operands):
            if kind == COL:
                vp = jnp.pad(v, (0, pn)) if pn else v
                operands.append(vp.astype(jnp.float32).reshape(1, -1))
                in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
            else:
                operands.append(
                    jnp.asarray(v, jnp.float32).reshape(1, 1))
                in_specs.append(pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)))

    y = pl.pallas_call(
        _make_kernel(tuple(rhs_ops)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        interpret=(backend == "pallas-interpret"),
        name=f"gemm_core.{autotune.ops_key(rhs_ops)}",
    )(xp, wp, *operands)
    return y[:M, :N].astype(out_dtype)


def _packed_gemm(x, w, rhs_ops, plan, backend, out_dtype):
    """`gemm` for a K-packed word stream: the packed body over the plan.

    x is laid out once, in XLA, in field-plane order — x3[i, m, r] =
    x[m, r * cpw + i], a small transpose of the (M, K) activations — so
    the kernel's x_i are leading-axis slices. The words are padded only
    where the plan's blocks do not tile them (never for widths that are
    multiples of the blocks, as the served shapes are)."""
    M, K = x.shape
    Kw, N = w.shape
    lead = rhs_ops[0]
    cpw = lead.k_pack
    bm, bn, _, bkw = plan
    Mp, Kwp, Np = _round_up(M, bm), _round_up(Kw, bkw), _round_up(N, bn)
    xp = jnp.pad(x.astype(_packed_dtype(x)),
                 ((0, Mp - M), (0, Kwp * cpw - K)))
    x3 = xp.reshape(Mp, Kwp, cpw).transpose(2, 0, 1)
    wp = (jnp.pad(w, ((0, Kwp - Kw), (0, Np - N)))
          if (Kwp, Np) != (Kw, N) else w)
    cols = [jnp.pad(v.astype(jnp.float32), (0, Np - N)).reshape(1, Np)
            for op in rhs_ops for v in op.operands]
    n_k = Kwp // bkw
    y = pl.pallas_call(
        _packed_kernel(lead.bits, n_k, len(cols)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[pl.BlockSpec((cpw, bm, bkw), lambda i, j, k: (0, i, k)),
                  pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j))]
        + [pl.BlockSpec((1, bn), lambda i, j, k: (0, j))] * len(cols),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                        if n_k > 1 else []),
        interpret=(backend == "pallas-interpret"),
        name=f"gemm_core.{autotune.ops_key(rhs_ops)}",
    )(x3, wp, *cols)
    return y[:M, :N] if (Mp, Np) != (M, N) else y


# ---------------------------------------------------------- tensor parallel
def tp_gemm(x: jax.Array, w: jax.Array, rhs_ops: tuple[RhsOp, ...] = (), *,
            mesh, axis: str = "model", blocks=None,
            backend: str | None = None, out_dtype=None) -> jax.Array:
    """Column-parallel y = x @ T(w) over one mesh axis via `shard_map`.

    The N dimension tiles across `axis`: the weight (and packed word
    stream — packing runs along K, so its columns split identically) and
    every per-column COL operand shard as P(None, axis) / P(axis), x
    replicates, and each device runs the full-K single-device `gemm` on
    its local (K, N/tp) shard. There is **no cross-device reduction** —
    each output column is produced wholly on one device with the exact
    single-device kernel arithmetic, so TP numerics are the 1-device
    numerics per column (the property the engine token-parity tests
    lean on). The returned array is the full (M, N) global result,
    laid out column-sharded over `axis`.

    Unlike a bare `gemm` inside a sharded program (an opaque custom call
    GSPMD would all-gather around — see `dispatch.backend_for_mesh`),
    the kernel here runs per device *inside* shard_map, so TPU hosts keep
    the MXU path under TP: the default backend is
    `dispatch.platform_default()`, not the mesh-demoted default.
    Block sizes resolve per *local* shape, so the autotune table tunes
    the (M, N/tp, K) shard, not the full width."""
    from jax.sharding import PartitionSpec as P

    tp = int(mesh.shape[axis])
    M, K = x.shape
    N = w.shape[1]
    if N % tp:
        raise ValueError(f"tp_gemm: N={N} must divide the {axis!r} axis "
                         f"size {tp}")
    backend = backend or dispatch.platform_default()

    in_specs = [P(), P(None, axis)]
    operands = []
    for op in rhs_ops:
        for kind, v in zip(op.kinds, op.operands):
            operands.append(v)
            in_specs.append(P(axis) if kind == COL else P())

    def body(xl, wl, *vals):
        i, ops_l = 0, []
        for op in rhs_ops:
            n = len(op.kinds)
            ops_l.append(dataclasses.replace(op, operands=vals[i:i + n]))
            i += n
        return gemm(xl, wl, tuple(ops_l), blocks=blocks, backend=backend,
                    out_dtype=out_dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, axis), check_vma=False)(
                             x, w, *operands)
