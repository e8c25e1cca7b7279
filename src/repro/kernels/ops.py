"""Public kernel entry points, routed through the backend dispatcher.

Every op resolves its execution backend via `repro.kernels.dispatch`
(pallas-tpu / pallas-interpret / xla-ref, per-call override supported) and
executes on the shared tiled-GEMM core (`gemm_core.gemm`) — the three seed
kernels' private tiling/padding/platform-sniffing copies are gone.

Matmul ops that sit on the training path (`matmul_op`, `masked_matmul_op`,
`fq_matmul_op`, `fq_masked_matmul_op`) carry custom VJPs: Pallas calls are
not generally differentiable, and the backward GEMMs reuse the same core
(the quantizer stays fused into the dx GEMM's RHS load; the weight
cotangent routes through `core.quant.fake_quant`'s elementwise STE VJP).
Column masks are GETA decay schedules, not learned parameters — their
cotangent is defined as zero (QASSO applies forgetting in the optimizer
update, never by backprop through the mask).

`fake_quant_op` exposes the fused elementwise kernel with the same
custom-VJP contract as `repro.core.quant.fake_quant`. Its legacy 5th
positional argument accepts None (dispatch default), a bool (interpret
mode), or a backend name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attn as _da
from repro.kernels import dispatch
from repro.kernels import fake_quant as _fq
from repro.kernels import gemm_core as _gc
from repro.kernels import introspect
from repro.kernels import ref as _ref
from repro.kernels import selective_scan as _ss
from repro.core.quant import fake_quant as _fake_quant_xla


def _fq_backend(interpret) -> bool:
    """Map the legacy interpret slot to the elementwise kernel's backend.

    Returns (use_xla_ref, interpret_flag)."""
    b = dispatch.resolve(None, interpret)
    return b == "xla-ref", b == "pallas-interpret"


# ----------------------------------------------------------------- fake quant
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fake_quant_op(x, d, q_m, t, interpret=None):
    use_ref, interp = _fq_backend(interpret)
    if use_ref:
        return _ref.fake_quant_fwd_ref(x, d, q_m, t)
    return _fq.fake_quant_fwd_pallas(x, d, q_m, t, interpret=interp)


def _fq_fwd(x, d, q_m, t, interpret):
    y = fake_quant_op(x, d, q_m, t, interpret)
    return y, (x, d, q_m, t)


def _fq_bwd(interpret, res, g):
    x, d, q_m, t = res
    use_ref, interp = _fq_backend(interpret)
    if use_ref:
        dx, dd, dqm, dt = _ref.fake_quant_bwd_ref(x, d, q_m, t, g)
    else:
        dx, dd, dqm, dt = _fq.fake_quant_bwd_pallas(x, d, q_m, t, g,
                                                    interpret=interp)
    return (dx, dd.reshape(jnp.shape(d)).astype(jnp.float32),
            dqm.reshape(jnp.shape(q_m)).astype(jnp.float32),
            dt.reshape(jnp.shape(t)).astype(jnp.float32))


fake_quant_op.defvjp(_fq_fwd, _fq_bwd)


# ------------------------------------------------------------- dense matmul
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul(x, w, backend):
    return _gc.gemm(x, w, (), backend=backend)


def _matmul_fwd(x, w, backend):
    return _matmul(x, w, backend), (x, w)


def _matmul_bwd(backend, res, g):
    x, w = res
    dx = _gc.gemm(g, w.T, (), backend=backend, out_dtype=x.dtype)
    dw = _gc.gemm(x.T, g, (), backend=backend, out_dtype=w.dtype)
    return dx, dw


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def matmul_op(x, w, *, interpret=None, backend=None):
    """y = x @ w on the shared GEMM core (differentiable)."""
    return _matmul(x, w, dispatch.resolve(backend, interpret))


# ------------------------------------------------------------- masked matmul
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _masked_matmul(x, w, mask, backend):
    return _gc.gemm(x, w, (_gc.col_mask(mask),), backend=backend)


def _mm_fwd(x, w, mask, backend):
    return _masked_matmul(x, w, mask, backend), (x, w, mask)


def _mm_bwd(backend, res, g):
    x, w, mask = res
    # d/dx [x @ (w*m)] = (g*m) @ w.T ; d/dw = (x.T @ g) * m = x.T @ (g*m).
    gm = (g.astype(jnp.float32) * mask.astype(jnp.float32)[None, :]
          ).astype(g.dtype)
    dx = _gc.gemm(gm, w.T, (), backend=backend, out_dtype=x.dtype)
    dw = _gc.gemm(x.T, g, (_gc.col_mask(mask),), backend=backend,
                  out_dtype=w.dtype)
    return dx, dw, jnp.zeros_like(mask)


_masked_matmul.defvjp(_mm_fwd, _mm_bwd)


def masked_matmul_op(x, w, mask, *, interpret=None, backend=None):
    """y = x @ (w * mask[None, :]) (differentiable; mask cotangent is 0)."""
    return _masked_matmul(x, w, mask, dispatch.resolve(backend, interpret))


# -------------------------------------------------------------- quant matmul
def quant_matmul_op(x, codes, scale, *, interpret=None, backend=None):
    """y = x @ (codes * scale[None, :]) — inference-only decode path."""
    backend = dispatch.resolve(backend, interpret)
    return _gc.gemm(x, codes, (_gc.dequant(scale),), backend=backend,
                    out_dtype=x.dtype)


def packed_quant_matmul_op(x, packed, bits, scale, *, interpret=None,
                           backend=None):
    """y = x @ (unpack(packed; bits) * scale[None, :]) — sub-byte serving.

    `packed` is the K-packed int32 word stream (`core.quant.pack_codes`,
    ceil(K/(32//bits)) rows for x: (M, K)); `bits` is the static storage
    width in [2, 8]. The words stream HBM->VMEM and decode inside VMEM via
    the `unpack_dequant` epilogue — inference-only, like `quant_matmul_op`."""
    backend = dispatch.resolve(backend, interpret)
    return _gc.gemm(x, packed, (_gc.unpack_dequant(bits, scale),),
                    backend=backend, out_dtype=x.dtype)


# --------------------------------------------------- flash-decode attention
def decode_attn_op(q, k, v, pos, *, layer=None, window=0, chunk=None,
                   interpret=None, backend=None):
    """Single-query flash-decode attention over one layer of the slot KV
    arena.

    q: (B, KVh, g, dh) query heads grouped per KV head (g = H // KVh);
    k/v: the stacked (nb, B, S, KVh * dh) arena as the engine stores it,
    read at `layer` (int or traced int32 scalar) in place, or with
    `layer` None one layer's (B, S, KVh, dh) rows — the current token's
    row already written; pos: (B,) int32 per-slot positions. Row b
    attends over its min(pos[b] + 1, S) valid rows — full and ring
    (windowed) arenas share the rule, enforced inside the kernel. Returns
    (B, KVh, g, dh) f32 — inference-only (decode holds no gradients),
    like `quant_matmul_op`. The split-K online-softmax kernel lives in
    `kernels.decode_attn`; the xla-ref backend slices the layer out,
    views it per head and runs the legacy einsum composition
    (`ref.decode_attn_ref`) bit-for-bit."""
    backend = dispatch.resolve(backend, interpret)
    if introspect.recording():
        # record the compiled-TPU tile geometry regardless of which
        # backend this trace routes to (see kernels.introspect)
        B, KVh, g, dh = q.shape
        gp, ch = _da.plan_tiles(g, k.shape[1 if layer is None else 2],
                                chunk)
        introspect.note(introspect.AttnLaunch(
            kind="decode_attn", B=B, KVh=KVh, g=g, dh=dh, gp=gp, dhs=dh,
            chunk=ch, backend=backend, kv_itemsize=k.dtype.itemsize))
    if backend == "xla-ref":
        if layer is not None:
            k, v = (_ref.arena_layer(a, layer, q.shape[1]) for a in (k, v))
        return _ref.decode_attn_ref(q, k, v, pos, window=window)
    return _da.decode_attn_pallas(q, k, v, pos, layer=layer, window=window,
                                  chunk=chunk,
                                  interpret=(backend == "pallas-interpret"))


def paged_decode_attn_op(q, kpool, vpool, pos, page_table, *, page_size,
                         seq_len, kv_bits=None, k_scale=None, v_scale=None,
                         window=0, interpret=None, backend=None):
    """Single-query flash-decode attention over a *paged* KV pool.

    kpool/vpool: (n_pages, page_size, KVh, dh) pool pages shared by every
    slot — or int8 codes (byte width dh for kv_bits=8, dh//2 nibble pairs
    for kv_bits=4) with per-row f32 scales k_scale/v_scale of shape
    (n_pages, page_size, KVh), decoded in VMEM by the kernel.
    page_table: (B, Lp) int32 logical->physical map; unallocated logical
    pages alias the reserved zero page. `seq_len` is the logical arena
    length the contiguous engine would use — the valid mask is the same
    min(pos+1, seq_len) rule, and the xla-ref backend's gathered view is
    sliced to exactly `seq_len` rows so an unquantized paged engine is
    bit-identical to the contiguous one (see `ref.paged_decode_attn_ref`).
    """
    backend = dispatch.resolve(backend, interpret)
    if introspect.recording():
        B, KVh, g, dh = q.shape
        dhs = kpool.shape[-1]
        gp = _da.plan_paged_tiles(g, dh, dhs, kv_bits)
        introspect.note(introspect.AttnLaunch(
            kind="paged_decode_attn", B=B, KVh=KVh, g=g, dh=dh, gp=gp,
            dhs=dhs, chunk=int(page_size), backend=backend,
            kv_itemsize=kpool.dtype.itemsize, scaled=kv_bits is not None))
    if backend == "xla-ref":
        return _ref.paged_decode_attn_ref(
            q, kpool, vpool, pos, page_table, page_size=page_size,
            seq_len=seq_len, kv_bits=kv_bits, k_scale=k_scale,
            v_scale=v_scale, window=window)
    return _da.paged_decode_attn_pallas(
        q, kpool, vpool, pos, page_table, page_size=page_size,
        seq_len=seq_len, kv_bits=kv_bits, k_scale=k_scale, v_scale=v_scale,
        window=window, interpret=(backend == "pallas-interpret"))


# ------------------------------------------- fused fake-quant (+mask) matmul
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fq_matmul(x, w, d, q_m, t, backend):
    return _gc.gemm(x, w, (_gc.fake_quant_rhs(d, q_m, t),), backend=backend)


def _fqm_fwd(x, w, d, q_m, t, backend):
    return _fq_matmul(x, w, d, q_m, t, backend), (x, w, d, q_m, t)


def _fq_weight_grads(w, d, q_m, t, dwq):
    """Route the weight cotangent through the quantizer's STE VJP
    (Eqs 4-6 for the scalars, clip-gated identity for w)."""
    _, vjp = jax.vjp(_fake_quant_xla, w, d, q_m, t)
    return vjp(dwq.astype(w.dtype))


def _fqm_bwd(backend, res, g):
    x, w, d, q_m, t = res
    # dx = g @ fake_quant(w).T; fake_quant is elementwise, so the transpose
    # commutes and the quantizer stays fused into the RHS tile load.
    fq = _gc.fake_quant_rhs(d, q_m, t)
    dx = _gc.gemm(g, w.T, (fq,), backend=backend, out_dtype=x.dtype)
    dwq = _gc.gemm(x.T, g, (), backend=backend, out_dtype=jnp.float32)
    dw, dd, dqm, dt = _fq_weight_grads(w, d, q_m, t, dwq)
    return dx, dw, dd, dqm, dt


_fq_matmul.defvjp(_fqm_fwd, _fqm_bwd)


def fq_matmul_op(x, w, d, q_m, t, *, interpret=None, backend=None):
    """y = x @ fake_quant(w; d, q_m, t) in one HBM pass of W.

    Backward: STE through the quantizer (via `core.quant.fake_quant`'s VJP,
    Eqs 4-6 for the scalars)."""
    return _fq_matmul(x, w, d, q_m, t, dispatch.resolve(backend, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fq_masked_matmul(x, w, mask, d, q_m, t, backend):
    return _gc.gemm(x, w, _gc.fq_mask_ops(d, q_m, t, mask), backend=backend)


def _fqmm_fwd(x, w, mask, d, q_m, t, backend):
    y = _fq_masked_matmul(x, w, mask, d, q_m, t, backend)
    return y, (x, w, mask, d, q_m, t)


def _fqmm_bwd(backend, res, g):
    x, w, mask, d, q_m, t = res
    # dx = g @ (fq(w)∘mask).T = (g∘mask) @ fq(w.T);
    # dwq = x.T @ g ∘ mask    = x.T @ (g∘mask).
    gm = (g.astype(jnp.float32) * mask.astype(jnp.float32)[None, :]
          ).astype(g.dtype)
    fq = _gc.fake_quant_rhs(d, q_m, t)
    dx = _gc.gemm(gm, w.T, (fq,), backend=backend, out_dtype=x.dtype)
    dwq = _gc.gemm(x.T, gm, (), backend=backend, out_dtype=jnp.float32)
    dw, dd, dqm, dt = _fq_weight_grads(w, d, q_m, t, dwq)
    return dx, dw, jnp.zeros_like(mask), dd, dqm, dt


_fq_masked_matmul.defvjp(_fqmm_fwd, _fqmm_bwd)


def fq_masked_matmul_op(x, w, mask, d, q_m, t, *, interpret=None,
                        backend=None):
    """y = x @ (fake_quant(w; d, q_m, t) * mask[None, :]).

    The GETA joint-stage forward in a single HBM pass of W (vs three for
    quantize -> mask -> matmul). Mask cotangent is 0 (decay schedule)."""
    return _fq_masked_matmul(x, w, mask, d, q_m, t,
                             dispatch.resolve(backend, interpret))


# ------------------------------------------------------------ selective scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _selective_scan(u, dt, Bm, Cm, A, h0, chunk, interpret):
    return _ss.selective_scan_pallas(u, dt, Bm, Cm, A, h0,
                                     interpret=interpret)


def _ss_fwd(u, dt, Bm, Cm, A, h0, chunk, interpret):
    return (_selective_scan(u, dt, Bm, Cm, A, h0, chunk, interpret),
            (u, dt, Bm, Cm, A, h0))


def _ss_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(_ref.selective_scan_ref, chunk=chunk),
                     *res)
    return vjp(g)


_selective_scan.defvjp(_ss_fwd, _ss_bwd)


def selective_scan_op(u, dt, Bm, Cm, A, h0, *, chunk=64, interpret=None,
                      backend=None):
    """Mamba-1's selective scan over S tokens from the state h0:
    (y (B, S, Di) f32 without the D skip, h_last (B, Di, N) f32); shapes
    as `ref.selective_scan_ref`. The Pallas kernel
    (`kernels.selective_scan`, one launch over the whole sequence) takes
    S > 1; a single decode token, and the xla-ref backend, run the
    chunked composition. Differentiable: the kernel's backward is the
    composition's."""
    backend = dispatch.resolve(backend, interpret)
    if backend == "xla-ref" or u.shape[1] == 1:
        return _ref.selective_scan_ref(u, dt, Bm, Cm, A, h0, chunk=chunk)
    return _selective_scan(u, dt, Bm, Cm, A, h0, chunk,
                           backend == "pallas-interpret")


# Re-export oracles for tests/benchmarks.
arena_layer = _ref.arena_layer
decode_attn_ref = _ref.decode_attn_ref
paged_decode_attn_ref = _ref.paged_decode_attn_ref
fake_quant_fwd_ref = _ref.fake_quant_fwd_ref
fake_quant_bwd_ref = _ref.fake_quant_bwd_ref
matmul_ref = _ref.matmul_ref
masked_matmul_ref = _ref.masked_matmul_ref
quant_matmul_ref = _ref.quant_matmul_ref
packed_quant_matmul_ref = _ref.packed_quant_matmul_ref
fq_matmul_ref = _ref.fq_matmul_ref
selective_scan_ref = _ref.selective_scan_ref
