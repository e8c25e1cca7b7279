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
Pallas kernel body *and* the xla-ref oracle backend.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import _EPS, clip_qmt, unpack_codes
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
    k_pack: >1 marks a bit-unpacking op: the RHS array is stored packed
           along K (`k_pack` codes per int32 word), `apply` receives the
           *raw integer* word tile of shape (bk/k_pack, bn) and must
           return the decoded f32 (bk, bn) tile. Only the first op may
           unpack (later ops see the dense decoded tile).
    """
    name: str
    kinds: tuple[str, ...]
    apply: Callable[..., jax.Array]
    operands: tuple[jax.Array, ...]
    k_pack: int = 1

    def __post_init__(self):
        assert len(self.kinds) == len(self.operands), (self.name, self.kinds)


# ------------------------------------------------------------- op factories
def col_mask(mask: jax.Array) -> RhsOp:
    """w *= mask[None, :] — structured column (pruning-group) mask."""
    return RhsOp("col_mask", (COL,), lambda w, m: w * m, (mask,))


def dequant(scale: jax.Array) -> RhsOp:
    """w = codes * scale[None, :] — int-code dequantization."""
    return RhsOp("dequant", (COL,), lambda w, s: w * s, (scale,))


def unpack_dequant(bits: int, scale: jax.Array) -> RhsOp:
    """Sub-byte decode: int32 K-packed words -> f32 codes * scale.

    The RHS streams HBM->VMEM as `core.quant.pack_codes` words (32//bits
    codes per word, LSB field first, packed along K); the tile is
    unpacked — shift, mask, sign-extend — and dequantized entirely inside
    VMEM, so a 4-bit site moves half the HBM bytes of its int8 container.
    Composes with later COL ops (`col_mask`) exactly like `dequant`."""
    bits = int(bits)
    if not 2 <= bits <= 8:
        raise ValueError(f"unpack_dequant bits must be in [2, 8]: {bits}")
    cpw = 32 // bits

    def apply(words, s):
        # words: (Wk, n) int32 — raw packed tile (k_pack routes it here
        # uncast); returns the decoded (Wk * cpw, n) f32 tile. The
        # shift/mask/sign-extend decode lives in `core.quant.unpack_codes`
        # only (pure jnp, kernel-body compatible), so the packing format
        # has exactly one definition.
        codes = unpack_codes(words, bits, words.shape[0] * cpw, axis=0)
        return codes.astype(jnp.float32) * s

    return RhsOp(f"unpack_dequant_b{bits}", (COL,), apply, (scale,),
                 k_pack=cpw)


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
        w = w_ref[...]
        if not (ops and ops[0].k_pack > 1):
            w = w.astype(jnp.float32)   # unpack ops consume the raw ints
        i = 0
        for op in ops:
            vals = [op_refs[i + j][...].astype(jnp.float32)
                    for j in range(len(op.kinds))]
            w = op.apply(w, *vals)
            i += len(op.kinds)
        o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

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


def plan_blocks(M: int, N: int, K: int, k_pack: int = 1, blocks=None
                ) -> tuple[int, int, int, int]:
    """Resolve the final (bm, bn, bk, bkw) tile `gemm` would launch for an
    (M, N, K) problem: the clamp rule above plus the packed-K word
    alignment (bk must cover whole words *and* keep both tiles MXU-legal:
    a multiple of the 128-lane x tiling with bk/k_pack a multiple of 8
    sublanes — lcm(k_pack*8, 128), a no-op 128 for bits 2/4/8 and 640 for
    the bits=3 10-codes stream). Shared by `gemm` and the static VMEM
    model (`kernels.introspect`) so the footprint the analyzer budgets is
    the tile the kernel actually dispatches."""
    bm, bn, bk = _clamp_blocks(blocks or DEFAULT_BLOCKS, M, N, K)
    if k_pack > 1:
        bk = math.lcm(k_pack * 8, max(bk, 128))
        return bm, bn, bk, bk // k_pack
    return bm, bn, bk, bk


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
    backend) if one was recorded, `DEFAULT_BLOCKS` otherwise — so TP
    shards and pruned widths don't run tiles sized for full shapes.
    Pass explicit blocks to bypass the table (parity tests do).
    """
    backend = dispatch.resolve(backend)
    M, K = x.shape
    k_pack = rhs_ops[0].k_pack if rhs_ops else 1
    assert all(op.k_pack == 1 for op in rhs_ops[1:]), \
        "only the leading RHS op may unpack"
    Kw, N = w.shape
    if k_pack > 1:
        assert Kw == -(-K // k_pack), (x.shape, w.shape, k_pack)
    else:
        assert K == Kw, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype

    if blocks is None:
        blocks = autotune.lookup(M, N, K, autotune.ops_key(rhs_ops),
                                 backend) or DEFAULT_BLOCKS

    plan = plan_blocks(M, N, K, k_pack, blocks)
    if introspect.recording():
        # the tile the compiled-TPU path would launch, recorded even when
        # this trace routes to xla-ref (CPU CI statically audits the TPU
        # footprint — see kernels.introspect)
        introspect.note(introspect.GemmLaunch(
            M=M, N=N, K=K, k_pack=k_pack,
            n_col=sum(k == COL for op in rhs_ops for k in op.kinds),
            n_scalar=sum(k == SCALAR for op in rhs_ops for k in op.kinds),
            ops=autotune.ops_key(rhs_ops), backend=backend, blocks=plan,
            w_itemsize=w.dtype.itemsize))

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

    bm, bn, bk, _ = plan
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    Mp, Kp = xp.shape
    if k_pack > 1:
        pkw, bkw = Kp // k_pack - Kw, bk // k_pack
    else:
        pkw, bkw = pk, bk
    wp = jnp.pad(w, ((0, pkw), (0, pn))) if (pkw or pn) else w
    Np = wp.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
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
    layout = []
    for op in rhs_ops:
        layout.append((op.name, op.kinds, op.apply, op.k_pack))
        for kind, v in zip(op.kinds, op.operands):
            operands.append(v)
            in_specs.append(P(axis) if kind == COL else P())

    def body(xl, wl, *vals):
        i, ops_l = 0, []
        for name, kinds, apply, k_pack in layout:
            ops_l.append(RhsOp(name, kinds, apply,
                               tuple(vals[i:i + len(kinds)]), k_pack=k_pack))
            i += len(kinds)
        return gemm(xl, wl, tuple(ops_l), blocks=blocks, backend=backend,
                    out_dtype=out_dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, axis), check_vma=False)(
                             x, w, *operands)
