"""Fused flash-decode attention over the slot KV arena (single query).

Decode-time attention is the one hot op the kernel backend didn't own:
`attn_apply`'s decode branch materializes a full-length f32 score tensor
(B, KVh, g, 1, S_max) over the *whole* arena row, masks the unwritten
slots with `jnp.where`, and softmaxes — three HBM round-trips of an array
that grows with max_seq. This kernel streams each cache row once:

  grid = (batch-slot b, split-K chunk c)   — c innermost

Every program takes *all* KV heads of its chunk: the arena
(B, S, KVh, dh) is viewed as (B, S, KVh * dh) — a free row-major
reshape — and one (chunk, KVh * dh) block streams per step. Its last two
dims are (8, 128)-aligned (chunk) or equal to the array's (KVh * dh), so
the block is legal for the TPU lowering at any head count and head
width; the per-head (chunk, dh) tiles are static lane slices in VMEM.
The q / output blocks are (KVh, gp, dh), again whole in their last two
dims. The slot's valid length rides in SMEM through scalar prefetch
(`PrefetchScalarGridSpec`), never as a (1, 1) VMEM block.

Chunk programs for one slot run consecutively (the same accumulator
pattern `gemm_core` uses for its K axis), carrying the online-softmax
state across chunks in VMEM scratch, per KV head:

  m  (KVh, gp, LANES) f32   running row max of the scores
  l  (KVh, gp, LANES) f32   running softmax denominator
  o  = the f32 output block itself, holding the *unnormalized*
       rescaled accumulator until the last chunk divides by l.

Per chunk and head: s = q @ k_chunk^T / sqrt(dh), masked to the slot's
valid length; m_new = max(m, max(s)); both l and o are rescaled by
exp(m - m_new) before accumulating exp(s - m_new) — the standard
flash-attention cross-chunk combine, so any chunking of the cache length
produces the same softmax (the chunk-count invariance test pins this).

GQA: all g = H // KVh query groups of one KV head are the (gp, dh) LHS
of both GEMMs, so each kv tile is read once per head, not once per
query head.

Valid-length / ring-window masking lives *inside* the kernel: a slot at
position `pos` has written exactly n_valid = min(pos + 1, S) arena rows —
rows [0, pos] of a full arena, or the whole ring once `pos` wraps a
windowed (ring_len = S) arena. Attention is permutation-invariant over
KV rows, so the ring's scrambled storage order needs no unscrambling;
columns >= n_valid are masked to -1e30 and chunks that start at or past
n_valid are skipped entirely (`@pl.when`), so scores for unwritten rows
are never computed. Their block index clamps to the slot's last valid
chunk, so the pipeline does not fetch them either.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Cache-length rows processed per grid step. 128 keeps the (g, chunk)
# probability tile lane-aligned on the MXU; the wrapper shrinks it for
# short arenas (interpret mode may go as low as 8).
DEFAULT_CHUNK = 128

_NEG_INF = -1e30
_LANES = 128     # scratch minor dim: m/l are logically (g, 1), stored
                 # lane-replicated so the VMEM tile stays MXU-shaped


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _init_state(o_ref, m_ref, l_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)


def _head_update(h, q, kt, vt, col0, n_valid, scale, o_ref, m_ref, l_ref):
    """One online-softmax step of KV head `h` over a (chunk, dh) tile."""
    s = jax.lax.dot_general(
        q, kt, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (gp, chunk)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + col0
    s = jnp.where(col < n_valid, s, _NEG_INF)
    m_prev = m_ref[h, :, :1]                             # (gp, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (gp, chunk)
    l_new = l_ref[h, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (gp, dh)
    o_ref[0, h] = o_ref[0, h] * alpha + pv
    m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
    l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _finalize(o_ref, l_ref):
    o_ref[0] = o_ref[0] / jnp.maximum(l_ref[:, :, :1], 1e-30)


def _kernel(nv_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            chunk: int, nchunks: int, scale: float, kvh: int, dh: int):
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        _init_state(o_ref, m_ref, l_ref)

    n_valid = nv_ref[b]

    @pl.when(c * chunk < n_valid)
    def _chunk():
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)                   # (gp, dh)
            kt = k_ref[0, :, h * dh:(h + 1) * dh].astype(jnp.float32)
            vt = v_ref[0, :, h * dh:(h + 1) * dh].astype(jnp.float32)
            _head_update(h, q, kt, vt, c * chunk, n_valid, scale,
                         o_ref, m_ref, l_ref)

    @pl.when(c == nchunks - 1)
    def _final():
        _finalize(o_ref, l_ref)


def plan_tiles(g: int, S: int, chunk: int | None = None, *,
               align: int = 128) -> tuple[int, int]:
    """Resolve the (gp, chunk) tile geometry the flash-decode kernel
    launches for g query groups over an S-row arena: the query-group rows
    pad to the 8-sublane tile, and the chunk aligns to `align` rows (128
    compiled, 8 interpret). The head width is never padded: blocks take
    whole rows of KVh * dh lanes. Shared by `decode_attn_pallas` and the
    static VMEM model (`kernels.introspect`) so they cannot drift."""
    chunk = int(chunk or DEFAULT_CHUNK)
    chunk = max(align, min(_round_up(chunk, align), _round_up(S, align)))
    return _round_up(g, 8), chunk


def plan_paged_tiles(g: int, dh: int, dhs: int, kv_bits: int | None) -> int:
    """gp for the paged kernel, whose chunk is the page: the stored byte
    width `dhs` (dh, or dh // 2 nibble pairs at kv_bits=4) streams
    unpadded and nibble unpack doubles it back to dh in VMEM. Shared with
    the static VMEM model like `plan_tiles`."""
    assert dhs == (dh // 2 if kv_bits == 4 else dh), (dh, dhs, kv_bits)
    return _round_up(g, 8)


def decode_attn_pallas(q, k, v, pos, *, window: int = 0,
                       chunk: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Single-query attention of q over the (k, v) slot arena.

    q:   (B, KVh, g, dh) — the token's query heads, grouped per KV head
         (g = H // KVh; GQA ratio 1 for MHA).
    k,v: (B, S, KVh, dh) — arena rows, post update of the current token.
    pos: (B,) int32 per-slot absolute positions of the token being
         decoded; row b has min(pos[b] + 1, S) valid arena rows (ring
         arenas wrap, full arenas write row `pos` directly — same rule).
    window: the layer's sliding window (static); kept for interface
         symmetry with `attn_apply` — a windowed layer's arena *is* the
         ring (S = ring_len), so the masking rule above already covers it.
    chunk: split-K chunk length along S (default 128, shrunk to cover
         short arenas). Returns (B, KVh, g, dh) f32.
    """
    del window   # the min(pos+1, S) rule covers ring and full arenas
    B, KVh, g, dh = q.shape
    assert k.shape == v.shape == (B, k.shape[1], KVh, dh), (
        q.shape, k.shape, v.shape)
    S = k.shape[1]
    scale = 1.0 / math.sqrt(dh)

    # Compiled TPU blocks want 128-row chunks; the interpreter (CPU
    # parity tier) runs any shape, so it may tile at the 8-sublane floor.
    align = 8 if interpret else 128
    gp, chunk = plan_tiles(g, S, chunk, align=align)
    Sp = _round_up(S, chunk)
    nchunks = Sp // chunk

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kp = kp.reshape(B, Sp, KVh * dh)
    vp = vp.reshape(B, Sp, KVh * dh)
    nv = jnp.minimum(jnp.asarray(pos, jnp.int32).reshape(B) + 1, S)

    def qmap(b, c, nv_ref):
        return (b, 0, 0, 0)

    def kvmap(b, c, nv_ref):
        # chunks past the slot's valid rows are skipped by the kernel;
        # pinning their block to the last valid chunk skips the fetch
        return (b, jnp.minimum(c, (nv_ref[b] - 1) // chunk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nchunks),
        in_specs=[
            pl.BlockSpec((1, KVh, gp, dh), qmap),
            pl.BlockSpec((1, chunk, KVh * dh), kvmap),
            pl.BlockSpec((1, chunk, KVh * dh), kvmap),
        ],
        out_specs=pl.BlockSpec((1, KVh, gp, dh), qmap),
        scratch_shapes=[
            pltpu.VMEM((KVh, gp, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((KVh, gp, _LANES), jnp.float32),   # running denom l
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, nchunks=nchunks,
                          scale=scale, kvh=KVh, dh=dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVh, gp, dh), jnp.float32),
        interpret=interpret,
    )(nv, qp, kp, vp)
    return out[:, :, :g]


def tp_decode_attn(q, k, v, pos, *, mesh, axis: str = "model",
                   window: int = 0, chunk: int | None = None,
                   backend: str | None = None) -> jax.Array:
    """KV-head-parallel flash decode over one mesh axis via `shard_map`.

    Decode attention is embarrassingly parallel over KV heads — softmax
    normalizes within a head and GQA groups ride their KV head — so the
    TP layout splits q on its head axis (1) and the k/v arenas on theirs
    (2), each device runs the single-device kernel over KVh/tp local
    heads, and the (B, KVh, g, dh) output concatenates over heads with
    **no cross-device reduction**: per-head numerics are exactly the
    1-device kernel's. `pos` replicates (it is per-slot, not per-head).

    Like `tp_gemm`, the kernel call sits *inside* shard_map where it is
    partitioned already, so the default backend is
    `dispatch.platform_default()` — TPU hosts keep the fused kernel
    under TP instead of the mesh-demoted einsum path."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as _ops
    from repro.kernels import dispatch

    tp = int(mesh.shape[axis])
    KVh = q.shape[1]
    if KVh % tp:
        raise ValueError(f"tp_decode_attn: KVh={KVh} must divide the "
                         f"{axis!r} axis size {tp}")
    backend = backend or dispatch.platform_default()

    def body(ql, kl, vl, pl_):
        return _ops.decode_attn_op(ql, kl, vl, pl_, window=window,
                                   chunk=chunk, backend=backend)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, None, axis), P(None, None, axis),
                  P()),
        out_specs=P(None, axis), check_vma=False)(q, k, v, pos)


def _page_dequant(w, scale, bits):
    """Decode one int8 code tile (P, dhs) to f32 rows in VMEM.

    Mirrors `core.quant.kv_quant_decode` on a 2D tile: arithmetic-shift
    nibble unpack for bits=4 (low nibble in byte order first), then the
    per-row scale ((P, 1)). Zero codes with zero scale stay exact zeros,
    so pool padding and zero-page rows contribute nothing to the dot
    products.
    """
    x = w.astype(jnp.int32)
    if bits == 4:
        lo = (x << 28) >> 28
        hi = (x << 24) >> 28
        x = jnp.stack([lo, hi], axis=-1).reshape(x.shape[0], x.shape[1] * 2)
    return x.astype(jnp.float32) * scale


def _paged_kernel(nv_ref, pt_ref, q_ref, k_ref, v_ref, *rest,
                  chunk: int, nchunks: int, scale: float, kv_bits,
                  kvh: int, dhs: int):
    if kv_bits is not None:
        ks_ref, vs_ref, o_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        _init_state(o_ref, m_ref, l_ref)

    n_valid = nv_ref[b]

    @pl.when(c * chunk < n_valid)
    def _chunk():
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)              # (gp, dh)
            kt = k_ref[0, :, h * dhs:(h + 1) * dhs]          # (P, dhs)
            vt = v_ref[0, :, h * dhs:(h + 1) * dhs]
            if kv_bits is not None:
                kt = _page_dequant(kt, ks_ref[0, :, h:h + 1], kv_bits)
                vt = _page_dequant(vt, vs_ref[0, :, h:h + 1], kv_bits)
            else:
                kt = kt.astype(jnp.float32)
                vt = vt.astype(jnp.float32)
            _head_update(h, q, kt, vt, c * chunk, n_valid, scale,
                         o_ref, m_ref, l_ref)

    @pl.when(c == nchunks - 1)
    def _final():
        _finalize(o_ref, l_ref)


def paged_decode_attn_pallas(q, kpool, vpool, pos, page_table, *, page_size,
                             seq_len, kv_bits=None, k_scale=None,
                             v_scale=None, window: int = 0,
                             interpret: bool = False) -> jax.Array:
    """Page-indirect flash decode: the split-K grid of `decode_attn_pallas`
    with the K-chunk axis walking *logical pages* and the physical page id
    scalar-prefetched from the slot's page table.

    kpool/vpool: (n_pages, page_size, KVh, dh) pool (dtype rows), or int8
    codes of byte width dh / dh//2 for kv_bits 8 / 4 plus per-row scales
    k_scale/v_scale (n_pages, page_size, KVh) f32, decoded in VMEM right
    after the tile load — the KV analogue of the weight `unpack_dequant`
    epilogue. Like the contiguous kernel, one program takes every KV head
    of its page: the pool is viewed as (n_pages, page_size, KVh * dhs)
    and the scales as (n_pages, page_size, KVh), blocks whole in their
    last two dims. page_table: (B, Lp) int32; both it and the per-slot
    valid length ride in scalar-prefetch SMEM (`PrefetchScalarGridSpec`),
    so the k/v BlockSpec index map can address page pt[b, c] directly and
    only a slot's own pages ever stream into VMEM. Chunk = page_size
    (must be a multiple of 8). `seq_len` is the logical arena length;
    masking is the same min(pos+1, seq_len) rule as the contiguous
    kernel. Returns (B, KVh, g, dh) f32.
    """
    del window
    B, KVh, g, dh = q.shape
    P = int(page_size)
    if P % 8:
        raise ValueError(f"page_size must be a multiple of 8, got {P}")
    Lp = page_table.shape[1]
    if Lp * P < seq_len:
        raise ValueError(f"page table covers {Lp * P} rows < seq_len {seq_len}")
    n_pages, dhs = kpool.shape[0], kpool.shape[-1]
    scale = 1.0 / math.sqrt(dh)

    gp = plan_paged_tiles(g, dh, dhs, kv_bits)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kp = kpool.reshape(n_pages, P, KVh * dhs)
    vp = vpool.reshape(n_pages, P, KVh * dhs)
    nv = jnp.minimum(jnp.asarray(pos, jnp.int32).reshape(B) + 1, seq_len)
    pt = jnp.asarray(page_table, jnp.int32)

    def qmap(b, c, nv_ref, pt_ref):
        return (b, 0, 0, 0)

    def pagemap(b, c, nv_ref, pt_ref):
        return (pt_ref[b, c], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KVh, gp, dh), qmap),
        pl.BlockSpec((1, P, KVh * dhs), pagemap),
        pl.BlockSpec((1, P, KVh * dhs), pagemap),
    ]
    operands = [qp, kp, vp]
    if kv_bits is not None:
        in_specs += [pl.BlockSpec((1, P, KVh), pagemap),
                     pl.BlockSpec((1, P, KVh), pagemap)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Lp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVh, gp, dh), qmap),
        scratch_shapes=[
            pltpu.VMEM((KVh, gp, _LANES), jnp.float32),
            pltpu.VMEM((KVh, gp, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, chunk=P, nchunks=Lp, scale=scale,
                          kv_bits=kv_bits, kvh=KVh, dhs=dhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVh, gp, dh), jnp.float32),
        interpret=interpret,
    )(nv, pt, *operands)
    return out[:, :, :g]
