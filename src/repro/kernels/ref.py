"""Pure-jnp oracles for every Pallas kernel in this package.

These are the source of truth the kernel tests assert against
(`assert_allclose(kernel(x), ref(x))` over shape/dtype sweeps).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.quant import _EPS, clip_qmt, pos_pow


def fake_quant_fwd_ref(x, d, q_m, t):
    """Eqs (1)-(2): nonlinear clip + symmetric uniform quantize-dequantize."""
    d32 = jnp.maximum(jnp.asarray(d, jnp.float32), _EPS)
    qm32 = jnp.asarray(q_m, jnp.float32)
    t32 = jnp.asarray(t, jnp.float32)
    sign = jnp.sign(x).astype(jnp.float32)
    xt = clip_qmt(jnp.abs(x).astype(jnp.float32), qm32, t32)
    return (d32 * jnp.round(xt / d32) * sign).astype(x.dtype)


def fake_quant_bwd_ref(x, d, q_m, t, g):
    """Eqs (4)-(6) + STE dx. Returns (dx, dd, dq_m, dt) with scalar reductions."""
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    d32 = jnp.maximum(jnp.asarray(d, jnp.float32), _EPS)
    qm32 = jnp.maximum(jnp.asarray(q_m, jnp.float32), _EPS)
    t32 = jnp.asarray(t, jnp.float32)

    ax = jnp.abs(x32)
    sign = jnp.sign(x32)
    inside = ax <= qm32
    safe_ax = jnp.maximum(ax, _EPS)

    dx = jnp.where(inside, g32, 0.0).astype(x.dtype)

    v = clip_qmt(ax, qm32, t32) / d32
    dd = jnp.sum(g32 * sign * (jnp.round(v) - v))

    base = jnp.where(inside, safe_ax, qm32)
    dt = jnp.sum(g32 * sign * pos_pow(base, t32) * jnp.log(base))

    dqm = jnp.sum(
        g32 * jnp.where(inside, 0.0, sign * t32 * jnp.power(qm32, t32 - 1.0))
    )
    return dx, dd, dqm, dt


def matmul_ref(x, w):
    """Plain dense y = x @ w at f32 accumulation."""
    return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)


def masked_matmul_ref(x, w, mask):
    """y = x @ (w * mask[None, :]) — structured column (group) masking."""
    w32 = w.astype(jnp.float32) * mask.astype(jnp.float32)[None, :]
    return (x.astype(jnp.float32) @ w32).astype(x.dtype)


def fq_matmul_ref(x, w, d, q_m, t, mask=None):
    """y = x @ (fake_quant(w) * mask) — the fused GETA joint-stage forward."""
    wq = fake_quant_fwd_ref(w, d, q_m, t).astype(jnp.float32)
    if mask is not None:
        wq = wq * mask.astype(jnp.float32)[None, :]
    return (x.astype(jnp.float32) @ wq).astype(x.dtype)


def quant_matmul_ref(x, codes, scale):
    """y = x @ (codes * scale[None, :]) — int8 weights, per-column scales."""
    w = codes.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def decode_attn_ref(q, k, v, pos, *, window=0):
    """Single-query attention over the slot KV arena — the oracle for
    `kernels.decode_attn`.

    q: (B, KVh, g, dh) query heads grouped per KV head; k/v: (B, S, KVh,
    dh) arena rows (current token already written); pos: (B,) int32. Row
    b attends over its min(pos[b] + 1, S) written arena rows — rows
    [0, pos] of a full arena, or the whole ring once a windowed arena
    wraps (attention is permutation-invariant over KV rows, so ring
    storage order is irrelevant). `window` is accepted for interface
    symmetry; the min(pos+1, S) rule already covers both arena kinds.

    Deliberately the exact einsum/softmax composition of the legacy
    `attn_apply` decode branch (same ops, same order), so the xla-ref
    backend is bit-identical to the pre-kernel path and the engine's
    kernel-on-vs-off token-identity smoke is exact, not approximate.
    """
    del window
    B, KVh, g, dh = q.shape
    S = k.shape[1]
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    qh = q.reshape(B, 1, KVh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    valid = (jnp.arange(S)[None, :]
             < jnp.minimum(pos + 1, S)[:, None])
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, KVh, g, dh)


def paged_decode_attn_ref(q, kpool, vpool, pos, page_table, *, page_size,
                          seq_len, kv_bits=None, k_scale=None, v_scale=None,
                          window=0):
    """Single-query attention over a paged KV pool — the oracle for the
    page-indirect flash-decode kernel.

    kpool/vpool: (n_pages, page_size, KVh, dh) pool pages (or int8 codes
    of width dh / dh//2 for kv_bits 8 / 4, with per-row scales
    k_scale/v_scale of shape (n_pages, page_size, KVh)); page_table:
    (B, Lp) int32 logical->physical page map per slot. Gathers each
    slot's pages, dequantizes if the pool is quantized, and slices the
    flattened rows to `seq_len` — the contiguous arena length — before
    delegating to `decode_attn_ref`.

    The slice is load-bearing for the paged-vs-contiguous token-identity
    contract: XLA's reduction grouping varies with the reduced length,
    so attention over Lp*page_size rows (trailing zeros included) is not
    bitwise the same as over seq_len rows even though the extra columns
    carry zero probability. With the slice, an unquantized pool's
    gathered view is bitwise the contiguous arena (unallocated logical
    pages alias the zero page, matching the arena's zero-init tail) and
    this function reduces to the exact legacy composition.
    """
    del window
    pt = jnp.asarray(page_table, jnp.int32)
    B, Lp = pt.shape
    P = int(page_size)
    if Lp * P < seq_len:
        raise ValueError(f"page table covers {Lp * P} rows < seq_len {seq_len}")

    def gather(pool, scale):
        pages = jnp.take(pool, pt, axis=0)        # (B, Lp, P, KVh, dh*)
        if kv_bits is not None:
            from repro.core.quant import kv_quant_decode
            pages = kv_quant_decode(pages, jnp.take(scale, pt, axis=0),
                                    kv_bits)
        rows = pages.reshape(B, Lp * P, *pages.shape[3:])
        return rows[:, :seq_len]

    return decode_attn_ref(q, gather(kpool, k_scale), gather(vpool, v_scale),
                           pos)


def packed_quant_matmul_ref(x, packed, bits, scale):
    """y = x @ (unpack(packed) * scale[None, :]) — sub-byte packed weights.

    packed: (ceil(K / (32//bits)), N) int32 word stream from
    `core.quant.pack_codes` (K-packed); unpacks to the (K, N) codes and
    dequantizes — the oracle for the `unpack_dequant` GEMM epilogue."""
    from repro.core.quant import unpack_codes
    codes = unpack_codes(packed, bits, x.shape[-1], axis=0)
    return quant_matmul_ref(x, codes, scale)
