"""Composable model layers (pure functions over a flat param dict).

Conventions:
- params: flat dict[str, jax.Array]; stacked layer tensors carry a leading
  (L,) axis and are consumed by lax.scan over the layer stack.
- qparams: dict[str, QuantParams]; weight sites are applied with
  `qw(params, qparams, name)` — fake-quant if a site exists, pass-through
  otherwise (so the same model code serves QAT and vanilla training).
- activations in cfg.dtype (bf16 default); softmax/norm/SSM state in f32.
- every init_* returns (params, axes) where axes maps each param to a tuple
  of *logical* axis names consumed by repro.distributed.sharding.
  Those names also drive tensor-parallel *serving* (DESIGN.md §4.12):
  `make_plan(mode="tp")` shards the head / mlp / vocab axes over the mesh's
  "model" axis, and `serving_axes_for` extends the mapping to the derived
  `.codes` / `.packed{bits}` / `.scale` leaves compressed serving adds —
  layer code never changes, GSPMD partitions the same einsums.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quant import PACKED_STORAGE_BITS, QuantParams, fake_quant
from repro.kernels import ops as Kops

Dtype = Any

# Route Dense/attention projections through the kernel dispatch layer
# (repro.kernels.dispatch: pallas-tpu on TPU, xla-ref elsewhere, per-call
# override). Flag-gated, default on; `set_kernel_dispatch(False)` restores
# the plain `x @ qw(...)` composition. See DESIGN.md §4.
_KERNEL_DISPATCH = {"enabled": True}

# Components whose 2-D weights execute through `dense_proj` (and therefore
# can consume `<name>.codes` / fuse their `.wq` quantizer into the GEMM).
# Single source of truth for transformer._prequantize and core.subnet.
ROUTED_COMPONENTS = ("attn", "mlp", "mamba", "rwkv", "shared")

# Sub-byte packed weights ride the param dict as `<name>.packed{bits}`
# (int32 word stream, K-packed) + `<name>.scale`: the storage width lives
# in the *key*, so it stays a static value through jit while the words
# scan-stack over the layer axis exactly like the dense tensor did.
# Derived from the producer's width set (`compress_lm` emits exactly these
# suffixes via `packed_storage_bits`) so the two can't drift.
PACKED_PARAM_BITS = tuple(sorted(PACKED_STORAGE_BITS, reverse=True))


@dataclasses.dataclass(frozen=True)
class LayerShapes:
    """Physical dims one sublayer executes at.

    The global `ModelConfig` states the *architecture*; a pruned subnet
    executes at *smaller* per-sublayer widths (surviving kv-head groups,
    MLP hidden units, experts, mamba channels, rwkv heads). Every apply
    below reshapes/derives against these dims, so the same layer code
    serves the dense model (`LayerShapes.from_config`) and a physically
    sliced one (`core.subnet.derive_slim_plan`). `d_model` is the residual
    width — non-prunable in every LM graph (embed/head pin it), carried
    anyway so the invariant is explicit.
    """
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    n_experts: int = 0
    mamba_inner: int = 0
    rwkv_heads: int = 0
    cm_hidden: int = 0

    @classmethod
    def from_config(cls, cfg: ModelConfig) -> "LayerShapes":
        return cls(
            d_model=cfg.d_model,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head,
            d_ff=cfg.d_ff,
            n_experts=cfg.moe.n_experts if cfg.moe else 0,
            mamba_inner=cfg.mamba.expand * cfg.d_model if cfg.mamba else 0,
            rwkv_heads=(cfg.d_model // cfg.rwkv.head_size) if cfg.rwkv else 0,
            cm_hidden=cfg.d_ff,
        )


def set_kernel_dispatch(enabled: bool) -> None:
    _KERNEL_DISPATCH["enabled"] = bool(enabled)


def kernel_dispatch_enabled() -> bool:
    return _KERNEL_DISPATCH["enabled"]


# Fused flash-decode attention (kernels/decode_attn.py) on the single-query
# decode branch. Default on; `set_decode_attn(False)` (or
# REPRO_DECODE_ATTN=0) restores the full-length einsum+softmax composition.
# The op dispatches like every other kernel (pallas-tpu on TPU, the
# bit-identical xla-ref oracle elsewhere), so flipping the flag on a CPU
# host changes which *code path* runs, not the tokens. See DESIGN.md §4.9.
_DECODE_ATTN = {"enabled": os.environ.get("REPRO_DECODE_ATTN", "1") != "0"}


def set_decode_attn(enabled: bool) -> None:
    _DECODE_ATTN["enabled"] = bool(enabled)


def decode_attn_enabled() -> bool:
    return _DECODE_ATTN["enabled"]


@contextlib.contextmanager
def use_decode_attn(enabled: bool):
    """Scoped flag flip (tests / parity smokes / benchmarks)."""
    prev = _DECODE_ATTN["enabled"]
    _DECODE_ATTN["enabled"] = bool(enabled)
    try:
        yield
    finally:
        _DECODE_ATTN["enabled"] = prev

# Optional NamedSharding for decode attention scores (B, KV, g, 1, S).
# When the KV cache is d_head-sharded (GQA kv-heads don't divide the model
# axis), XLA's default strategy re-gathers the whole cache per step
# ('involuntary full rematerialization'); pinning the score sharding makes
# it contract d_head locally and psum the (small) partial scores instead.
# Set by launch/dryrun (serve_attn='psum'); None = compiler's choice.
DECODE_SCORE_SHARDING = None


@dataclasses.dataclass(frozen=True)
class PagedView:
    """How one decode step addresses the paged KV pool (DESIGN.md §4.11).

    `table` is the (B, Lp) logical->physical page map (traced; rebuilt
    inside each jitted step from the engine's host table). The rest is
    static geometry: `page_size` rows per page, `seq_len` the *logical*
    arena length — attention masks/slices to exactly this many rows so
    an unquantized paged decode is bitwise the contiguous arena's —
    and `kv_bits` (None | 8 | 4) selecting the quantized page store.
    """
    table: jax.Array
    page_size: int
    seq_len: int
    kv_bits: Optional[int] = None


def _dt(cfg: ModelConfig) -> Dtype:
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def qw(params: dict, qparams: Optional[dict], name: str) -> jax.Array:
    """Weight fetch through the (optional) parameterized quantizer."""
    w = params[name]
    site = name + ".wq"
    if qparams is not None and site in qparams:
        qp: QuantParams = qparams[site]
        w = fake_quant(w, qp.d, qp.q_m, qp.t)
    return w


def qa(x: jax.Array, qparams: Optional[dict], site: str) -> jax.Array:
    """Activation pass through the (optional) parameterized quantizer."""
    if qparams is not None and site in qparams:
        qp: QuantParams = qparams[site]
        x = fake_quant(x, qp.d, qp.q_m, qp.t)
    return x


def dense_proj(x: jax.Array, lp: dict, qp: Optional[dict], name: str, *,
               mask: Optional[jax.Array] = None,
               backend: Optional[str] = None) -> jax.Array:
    """Dense projection x @ (fake_quant(w) * mask), kernel-dispatch routed.

    One entry point for every 2-D weight projection:
    - dense weight, no quant site      -> matmul_op
    - dense weight + weight-quant site -> fq_matmul_op (fused fake-quant
      epilogue: one HBM pass of W instead of quantize -> matmul)
    - + column mask (GETA joint stage) -> fq_masked_matmul_op /
      masked_matmul_op (mask fused into the RHS tile load)
    - int codes (`<name>.codes` / `<name>.scale` from a compressed Subnet)
      -> quant_matmul_op (dequant inside VMEM; the serving path)
    - packed sub-byte codes (`<name>.packed{bits}` + `<name>.scale`)
      -> packed_quant_matmul_op (unpack-dequant inside VMEM; int32 words
      at 32//bits codes each stream from HBM — the `--packed` path)

    A column mask may also ride the param dict as `<name>.colmask` so it
    stacks over the layer axis and scans with the block body. Every op it
    emits sits under the `proj` name scope (the device trace's weight
    stream).
    """
    with jax.named_scope("proj"):
        return _dense_proj(x, lp, qp, name, mask=mask, backend=backend)


def _dense_proj(x, lp, qp, name, *, mask, backend):
    codes = lp.get(name + ".codes")
    if mask is None:
        mask = lp.get(name + ".colmask")
    site = name + ".wq"
    qpw: Optional[QuantParams] = qp.get(site) if qp is not None else None

    for pbits in PACKED_PARAM_BITS:
        packed = lp.get(f"{name}.packed{pbits}")
        if packed is not None:
            scale = jnp.asarray(lp[name + ".scale"], jnp.float32)
            if scale.ndim == 0:
                scale = jnp.broadcast_to(scale, (packed.shape[-1],))
            x2 = x.reshape(-1, x.shape[-1])
            y = Kops.packed_quant_matmul_op(x2, packed, pbits, scale,
                                            backend=backend)
            return y.reshape(*x.shape[:-1], packed.shape[-1])

    if codes is not None:
        scale = jnp.asarray(lp[name + ".scale"], jnp.float32)
        if scale.ndim == 0:
            scale = jnp.broadcast_to(scale, (codes.shape[-1],))
        x2 = x.reshape(-1, x.shape[-1])
        y = Kops.quant_matmul_op(x2, codes, scale, backend=backend)
        return y.reshape(*x.shape[:-1], codes.shape[-1])

    w = lp[name]
    if not kernel_dispatch_enabled() or w.ndim != 2 \
            or (qpw is None and mask is None):
        # plain dense (or flag off): XLA's native dot is the fastest
        # correct path and — unlike an opaque pallas_call — partitions
        # under GSPMD. The kernels only earn their keep when there is an
        # epilogue to fuse.
        if qpw is not None:
            w = fake_quant(w, qpw.d, qpw.q_m, qpw.t)
        if mask is not None:
            w = w * mask.astype(w.dtype)[None, :]
        return x @ w

    x2 = x.reshape(-1, x.shape[-1])
    if qpw is not None and mask is not None:
        y = Kops.fq_masked_matmul_op(x2, w, mask, qpw.d, qpw.q_m, qpw.t,
                                     backend=backend)
    elif qpw is not None:
        y = Kops.fq_matmul_op(x2, w, qpw.d, qpw.q_m, qpw.t, backend=backend)
    else:
        y = Kops.masked_matmul_op(x2, w, mask, backend=backend)
    return y.reshape(*x.shape[:-1], w.shape[-1])


# ------------------------------------------------------------------ norms
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("pointwise"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def groupnorm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    n_heads: int, eps: float = 1e-5) -> jax.Array:
    """Per-head groupnorm (RWKV ln_x). x: (..., H*dh)."""
    shp = x.shape
    x32 = x.astype(jnp.float32).reshape(*shp[:-1], n_heads, -1)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(shp)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------- rope
def rope_tables(seq_len: int, d_head: int, theta: float,
                offset: int = 0) -> tuple[jax.Array, jax.Array]:
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    freqs = theta ** (-jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head)
    ang = pos[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, dh); cos/sin: (S, dh/2), or (B, S, dh/2) when every
    sequence sits at its own absolute position (per-slot decode)."""
    with jax.named_scope("pointwise"):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        if cos.ndim == 3:
            c = cos[:, :, None, :]
            s = sin[:, :, None, :]
        else:
            c = cos[None, :, None, :]
            s = sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                               axis=-1).astype(x.dtype)


# -------------------------------------------------------------- attention
def _causal_mask(sq: int, sk: int, q_off: int, window: int) -> jax.Array:
    qi = jnp.arange(sq)[:, None] + q_off
    ki = jnp.arange(sk)[None, :]
    m = ki <= qi
    if window > 0:
        m = jnp.logical_and(m, ki > qi - window)
    return m


def attention_dense(q, k, v, *, window: int = 0, q_offset: int = 0,
                    causal: bool = True):
    """Full materialized attention (exact; used when S is modest).

    q: (B, Sq, H, dh); k/v: (B, Sk, KV, dh) — GQA handled by reshape.
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qh = q.reshape(B, Sq, KV, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    if causal:
        mask = _causal_mask(Sq, k.shape[1], q_offset, window)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def attention_blockwise(q, k, v, *, block: int = 1024, window: int = 0):
    """Flash-style online-softmax attention (never materializes S x S).

    Outer scan over query blocks; inner scan over KV blocks with running
    (max, denom, acc). Exact (same math as attention_dense).

    Both loop bodies are jax.checkpoint'ed so the backward pass *recomputes*
    the block scores instead of saving them — without this, the scan VJPs
    persist every (q-block x kv-block) score tile simultaneously during the
    layer backward (measured +17 GB/device at 4k seq on internlm2-1.8b).
    """
    B, S, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    nb = S // block
    assert S % block == 0, (S, block)
    qb = q.reshape(B, nb, block, KV, g, dh)
    kb = k.reshape(B, nb, block, KV, dh)
    vb = v.reshape(B, nb, block, KV, dh)

    def q_block(qi, q_i):
        # q_i: (B, block, KV, g, dh)
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_j, v_j = inp
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) / math.sqrt(dh)
            mask = _causal_mask(block, block, (qi - ki) * block, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v_j.astype(jnp.float32))
            # skip fully-masked future blocks (they contribute zeros anyway,
            # masked by -1e30 -> exp ~ 0)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, g, block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, g, block), jnp.float32)
        a0 = jnp.zeros((B, KV, g, block, dh), jnp.float32)
        ks = jnp.arange(nb)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0),
            (ks, jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1)  # (B, block, KV, g, dh)

    outs = jax.lax.map(jax.checkpoint(lambda args: q_block(*args)),
                       (jnp.arange(nb), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, dh)
    return out.astype(q.dtype)


def attention(q, k, v, cfg: ModelConfig, *, window: int = 0,
              q_offset: int = 0):
    S = q.shape[1]
    if S > cfg.attn_block_threshold and S % cfg.attn_block_size == 0 \
            and q.shape[1] == k.shape[1]:
        return attention_blockwise(q, k, v, block=cfg.attn_block_size,
                                   window=window)
    return attention_dense(q, k, v, window=window, q_offset=q_offset)


# --------------------------------------------------------- attention block
def init_attention(key, cfg: ModelConfig, prefix: str, n_layers: int,
                   dtype) -> tuple[dict, dict]:
    D, Q, KVd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = D ** -0.5
    L = (n_layers,) if n_layers else ()
    lax_ = ("layers",) if n_layers else ()
    p = {
        f"{prefix}.wq": jax.random.normal(k1, L + (D, Q), dtype) * std,
        f"{prefix}.wk": jax.random.normal(k2, L + (D, KVd), dtype) * std,
        f"{prefix}.wv": jax.random.normal(k3, L + (D, KVd), dtype) * std,
        f"{prefix}.wo": jax.random.normal(k4, L + (Q, D), dtype) * std,
    }
    axes = {
        f"{prefix}.wq": lax_ + ("embed", "q_heads"),
        f"{prefix}.wk": lax_ + ("embed", "kv_heads"),
        f"{prefix}.wv": lax_ + ("embed", "kv_heads"),
        f"{prefix}.wo": lax_ + ("q_heads", "embed"),
    }
    if cfg.qkv_bias:
        p[f"{prefix}.bq"] = jnp.zeros(L + (Q,), dtype)
        p[f"{prefix}.bk"] = jnp.zeros(L + (KVd,), dtype)
        p[f"{prefix}.bv"] = jnp.zeros(L + (KVd,), dtype)
        axes[f"{prefix}.bq"] = lax_ + ("q_heads",)
        axes[f"{prefix}.bk"] = lax_ + ("kv_heads",)
        axes[f"{prefix}.bv"] = lax_ + ("kv_heads",)
    return p, axes


def _rows(x, dtype):
    """(B, S, KVh, dh) keys or values as cache rows (B, S, KVh * dh)."""
    return x.reshape(x.shape[:2] + (-1,)).astype(dtype)


def _heads(c, kvh: int):
    """(B, S, KVh * dh) cache rows viewed per head: (B, S, KVh, dh)."""
    return c.reshape(c.shape[:2] + (kvh, c.shape[-1] // kvh))


def attn_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
               rope: Optional[tuple], window: int = 0, prefix: str,
               cache: Optional[tuple] = None, q_offset: int = 0,
               shapes: Optional[LayerShapes] = None, chunked: bool = False,
               pages: Optional[PagedView] = None, layer=None):
    """lp: per-layer (unstacked) params view. `rope` is the (cos, sin)
    tables of the queries' positions, or None for attention with no
    positional encoding. cache: (k_cache, v_cache,
    write_pos) for decode; a contiguous cache stores one layer's K and V
    as (B, S, KVh * dh) rows. With `layer` (an int or traced int32
    scalar), the decode branch's k/v are instead the stacked
    (nb, B, S, KVh * dh) arenas: the token's row is written at (layer,
    b, pos_b) and the kernel reads that layer in place, and the same
    stacked buffers come back. `shapes` carries this sublayer's physical
    dims (pruned subnets run fewer heads than the config states); default is
    the dense config. `chunked` scores an S-token chunk mid-sequence
    against the live cache (the speculative verify pass) instead of
    treating S > 1 as a from-scratch prefill. With `pages`, the decode
    branch treats the cache k/v as paged *pools* ((n_pages, P, KVh, dh*)
    + optional per-row scale planes appended to the cache tuple) and
    scatter-writes / page-gathers through the view's table instead of
    row-indexing a per-slot arena. Returns (out, new_cache)."""
    B, S, D = x.shape
    shapes = shapes or LayerShapes.from_config(cfg)
    H, KVh, dh = shapes.n_heads, shapes.n_kv_heads, shapes.d_head
    q = dense_proj(x, lp, qp, f"{prefix}.wq")
    k = dense_proj(x, lp, qp, f"{prefix}.wk")
    v = dense_proj(x, lp, qp, f"{prefix}.wv")
    if cfg.qkv_bias:
        q = q + lp[f"{prefix}.bq"]
        k = k + lp[f"{prefix}.bk"]
        v = v + lp[f"{prefix}.bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KVh, dh)
    v = v.reshape(B, S, KVh, dh)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None and chunked:
        # chunked verify (speculative decoding): append S contiguous rows
        # at each slot's own position and attend all S queries over the
        # arena at once. Query i sits at absolute position pos[b]+i, so it
        # sees arena rows [0, pos[b]+i] — the causal prefix including the
        # rows this very chunk just wrote. Full arenas only: a ring write
        # can overwrite pre-wrap rows, which a rejection could then never
        # roll back (the engine gates speculation on window == 0).
        if window > 0:
            raise ValueError(
                f"{prefix}: chunked cache scoring needs a full (non-ring) "
                f"arena; window={window} layers overwrite rows on wrap")
        ck, cv, pos = cache
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        row_upd = lambda c, u, s: jax.lax.dynamic_update_slice(
            c, u, (s, 0))
        with jax.named_scope("kv_write"):
            ck = jax.vmap(row_upd)(ck, _rows(k, ck.dtype), pos)
            cv = jax.vmap(row_upd)(cv, _rows(v, cv.dtype), pos)
        g = H // KVh
        qh = q.reshape(B, S, KVh, g, dh)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                            _heads(ck, KVh).astype(jnp.float32)
                            ) / math.sqrt(dh)
        valid = (jnp.arange(ck.shape[1])[None, None, :]
                 <= pos[:, None, None] + jnp.arange(S)[None, :, None])
        scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                         _heads(cv, KVh).astype(jnp.float32))
        out = out.reshape(B, S, H * dh).astype(x.dtype)
        out = qa(out, qp, f"{prefix}.attn_out.aq")
        return dense_proj(out, lp, qp, f"{prefix}.wo"), (ck, cv, pos + S)
    if cache is not None and S > 1:
        # one-shot prefill: write the whole prompt's K/V at positions
        # [0, S) in a single slice update and attend causally over the
        # prompt itself — no cache read, so a fresh (zeroed) cache row is
        # required. Windowed layers ring-wrap per token; a one-shot write
        # is only position-faithful while the prompt fits the ring.
        ck, cv, pos = cache
        assert window <= 0 or S <= ck.shape[1], (S, ck.shape[1])
        with jax.named_scope("kv_write"):
            ck = jax.lax.dynamic_update_slice(ck, _rows(k, ck.dtype),
                                              (0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, _rows(v, cv.dtype),
                                              (0, 0, 0))
        out = attention(q, k, v, cfg, window=window, q_offset=0)
        out = out.reshape(B, S, H * dh)
        out = qa(out, qp, f"{prefix}.attn_out.aq")
        return dense_proj(out, lp, qp, f"{prefix}.wo"), (ck, cv, pos + S)
    if cache is not None and pages is not None:
        # paged decode: the cache tuple holds shared *pools* — scatter the
        # token's K/V row at its slot's physical row (page_table[pos // P]
        # * P + pos % P) and attend through the page-indirect kernel.
        # Idle slots' tables point every logical page at the reserved
        # trash page, so their (discarded) writes can't touch live pages.
        if window > 0:
            raise ValueError(
                f"{prefix}: the paged arena needs full (non-ring) caches; "
                f"window={window} layers ring-wrap rows")
        if len(cache) == 5:
            ck, cv, pos, ksc, vsc = cache
        else:
            (ck, cv, pos), ksc, vsc = cache, None, None
        P = pages.page_size
        n_pages = ck.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        phys = jnp.take_along_axis(pages.table, (pos // P)[:, None],
                                   axis=1)[:, 0] * P + pos % P      # (B,)
        rowk, rowv = k[:, 0], v[:, 0]                     # (B, KVh, dh)
        with jax.named_scope("kv_write"):
            if pages.kv_bits is not None:
                from repro.core.quant import kv_quant_encode
                rowk, rsk = kv_quant_encode(rowk, pages.kv_bits)
                rowv, rsv = kv_quant_encode(rowv, pages.kv_bits)
                ksc = ksc.reshape(n_pages * P, KVh).at[phys].set(
                    rsk).reshape(ksc.shape)
                vsc = vsc.reshape(n_pages * P, KVh).at[phys].set(
                    rsv).reshape(vsc.shape)
            flat = (n_pages * P,) + ck.shape[2:]
            ck = ck.reshape(flat).at[phys].set(
                rowk.astype(ck.dtype)).reshape(ck.shape)
            cv = cv.reshape(flat).at[phys].set(
                rowv.astype(cv.dtype)).reshape(cv.shape)
        g = H // KVh
        use_kernel = (_DECODE_ATTN["enabled"] and _KERNEL_DISPATCH["enabled"]
                      and DECODE_SCORE_SHARDING is None)
        with jax.named_scope("attn_core"):
            out = Kops.paged_decode_attn_op(
                q.reshape(B, KVh, g, dh), ck, cv, pos, pages.table,
                page_size=P, seq_len=pages.seq_len, kv_bits=pages.kv_bits,
                k_scale=ksc, v_scale=vsc, window=window,
                backend=(None if use_kernel else "xla-ref"))
        out = out.reshape(B, 1, H, dh).astype(x.dtype)
        out = out.reshape(B, S, H * dh)
        out = qa(out, qp, f"{prefix}.attn_out.aq")
        new_cache = (ck, cv, pos + 1, ksc, vsc)
        return dense_proj(out, lp, qp, f"{prefix}.wo"), new_cache
    if cache is not None:
        ck, cv, pos = cache
        # decode: append the new token at `pos` (ring for windowed layers).
        # pos may be a scalar (static batch, every sequence in lockstep) or
        # a (B,) vector (continuous batching: every slot at its own
        # progress); both normalize to the per-row path.
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        one_layer = layer is None       # a (B, S, KVh*dh) cache: one layer
        if one_layer:
            ck, cv, layer = ck[None], cv[None], 0
        slot = jnp.mod(pos, ck.shape[2]) if window > 0 else pos
        # one in-place row write per slot into the stacked arena
        rk = _rows(k, ck.dtype).reshape(B, 1, 1, 1, KVh * dh)
        rv = _rows(v, cv.dtype).reshape(B, 1, 1, 1, KVh * dh)
        with jax.named_scope("kv_write"):
            for b in range(B):
                at = (layer, b, slot[b], 0)
                ck = jax.lax.dynamic_update_slice(ck, rk[b], at)
                cv = jax.lax.dynamic_update_slice(cv, rv[b], at)
        # attention of the single query over this layer of the arena
        g = H // KVh
        with jax.named_scope("attn_core"):
            if (_DECODE_ATTN["enabled"] and _KERNEL_DISPATCH["enabled"]
                    and DECODE_SCORE_SHARDING is None):
                # fused flash-decode kernel: split-K online softmax over the
                # arena, valid-length/ring masking inside the kernel
                out = Kops.decode_attn_op(q.reshape(B, KVh, g, dh),
                                          ck, cv, pos, layer=layer,
                                          window=window)
                out = out.reshape(B, 1, H, dh).astype(x.dtype)
            else:
                k_all = Kops.arena_layer(ck, layer, KVh)
                v_all = Kops.arena_layer(cv, layer, KVh)
                qh = q.reshape(B, 1, KVh, g, dh)
                scores = jnp.einsum("bqkgd,bskd->bkgqs",
                                    qh.astype(jnp.float32),
                                    k_all.astype(jnp.float32)) / math.sqrt(dh)
                if DECODE_SCORE_SHARDING is not None:
                    scores = jax.lax.with_sharding_constraint(
                        scores, DECODE_SCORE_SHARDING)
                # mask unwritten arena rows: row b has written exactly
                # min(pos[b]+1, S) slots — rows [0, pos] of a full arena, or
                # the whole ring once a windowed arena wraps (softmax is
                # permutation-invariant over KV rows, so ring order is moot).
                # A fresh (pos < ring_len) windowed cache *must* mask its
                # zero-initialized tail, same as the full arena.
                n_rows = k_all.shape[1]
                valid = (jnp.arange(n_rows)[None, :]
                         < jnp.minimum(pos + 1, n_rows)[:, None])
                scores = jnp.where(valid[:, None, None, None, :], scores,
                                   -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                                 v_all.astype(jnp.float32))
                out = out.reshape(B, 1, H, dh).astype(x.dtype)
        if one_layer:
            ck, cv = ck[0], cv[0]
        new_cache = (ck, cv, pos + 1)
    else:
        out = attention(q, k, v, cfg, window=window, q_offset=q_offset)
    out = out.reshape(B, S, H * dh)
    out = qa(out, qp, f"{prefix}.attn_out.aq")
    return dense_proj(out, lp, qp, f"{prefix}.wo"), new_cache


# -------------------------------------------------------------------- mlp
def init_mlp(key, cfg: ModelConfig, prefix: str, n_layers: int, dtype,
             d_ff: Optional[int] = None) -> tuple[dict, dict]:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    L = (n_layers,) if n_layers else ()
    lax_ = ("layers",) if n_layers else ()
    p = {
        f"{prefix}.w_gate": jax.random.normal(k1, L + (D, F), dtype) * D ** -0.5,
        f"{prefix}.w_up": jax.random.normal(k2, L + (D, F), dtype) * D ** -0.5,
        f"{prefix}.w_down": jax.random.normal(k3, L + (F, D), dtype) * F ** -0.5,
    }
    axes = {
        f"{prefix}.w_gate": lax_ + ("embed", "mlp"),
        f"{prefix}.w_up": lax_ + ("embed", "mlp"),
        f"{prefix}.w_down": lax_ + ("mlp", "embed"),
    }
    return p, axes


def mlp_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
              prefix: str):
    g = dense_proj(x, lp, qp, f"{prefix}.w_gate")
    u = dense_proj(x, lp, qp, f"{prefix}.w_up")
    with jax.named_scope("pointwise"):
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        h = qa(h, qp, f"{prefix}.mlp_act.aq")
    return dense_proj(h, lp, qp, f"{prefix}.w_down")


# -------------------------------------------------------------------- moe
def init_moe(key, cfg: ModelConfig, prefix: str, n_layers: int, dtype
             ) -> tuple[dict, dict]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    L = (n_layers,) if n_layers else ()
    lax_ = ("layers",) if n_layers else ()
    p = {
        f"{prefix}.router": jax.random.normal(k1, L + (D, E), dtype) * D ** -0.5,
        f"{prefix}.we_gate": jax.random.normal(k2, L + (E, D, F), dtype) * D ** -0.5,
        f"{prefix}.we_up": jax.random.normal(k3, L + (E, D, F), dtype) * D ** -0.5,
        f"{prefix}.we_down": jax.random.normal(k4, L + (E, F, D), dtype) * F ** -0.5,
    }
    axes = {
        f"{prefix}.router": lax_ + ("embed", "experts_router"),
        f"{prefix}.we_gate": lax_ + ("experts", "embed", "expert_mlp"),
        f"{prefix}.we_up": lax_ + ("experts", "embed", "expert_mlp"),
        f"{prefix}.we_down": lax_ + ("experts", "expert_mlp", "embed"),
    }
    if cfg.moe.shared_expert:
        ps, axs = init_mlp(key, cfg, f"{prefix}.shared", n_layers, dtype)
        p.update(ps)
        axes.update(axs)
    return p, axes


def moe_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
              prefix: str, full_capacity: bool = False,
              shapes: Optional[LayerShapes] = None):
    """Top-k token-choice MoE, GShard-style grouped einsum dispatch.

    Tokens are split into G groups (one per sequence) with *per-group*
    capacity C = cf * n * k / E; the dispatch one-hot is (G, n, E, C) —
    linear in tokens. A global-capacity formulation is quadratic in tokens
    (measured ~1 TB/device temp on jamba train_4k) because C grows with N
    while the mask still spans all N tokens.

    `full_capacity` sets C = n * K so no token is ever dropped — the
    serving semantics. One-token decode can never overflow an expert, so a
    one-shot prefill only matches the sequential decode loop if its
    prompt tokens don't compete for capacity either (capacity pressure is
    a training-time load-balancing device, not an inference behaviour).

    Sharding: groups ride the batch axes; annotating the dispatched
    activations with experts -> 'model' (cfg.moe.impl='alltoall') makes
    GSPMD lower dispatch/combine to all-to-all (the §Perf EP lever).
    """
    B, S, D = x.shape
    shapes = shapes or LayerShapes.from_config(cfg)
    E, K = shapes.n_experts, cfg.moe.top_k
    if E < K:
        raise ValueError(f"{prefix}: {E} surviving experts < top_k={K} — "
                         f"the expert family was pruned below the router's "
                         f"top-k (keep at least top_k experts)")
    G, n = B, S
    xg = x.reshape(G, n, D)
    logits = (xg @ qw(lp, qp, f"{prefix}.router")).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (G, n, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)           # (G, n, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    C = n * K if full_capacity \
        else max(int(cfg.moe.capacity_factor * n * K / E), 4)

    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (G, n, K, E)
    # position of each (token, k) within its expert's per-group queue
    flat = onehot.reshape(G, n * K, E)
    pos = jnp.cumsum(flat, axis=1).reshape(G, n, K, E) - 1.0
    pos = jnp.sum(pos * onehot, axis=-1)                     # (G, n, K)
    keep = (pos < C).astype(jnp.float32)
    gate_vals = gate_vals * keep

    posoh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=x.dtype)
    dispatch = jnp.einsum("gnke,gnkc->gnec", onehot.astype(x.dtype), posoh)
    combine = jnp.einsum("gnke,gnkc,gnk->gnec", onehot,
                         posoh.astype(jnp.float32), gate_vals)

    xe = jnp.einsum("gnec,gnd->gecd", dispatch, xg)          # (G, E, C, D)
    g = jnp.einsum("gecd,edf->gecf", xe, qw(lp, qp, f"{prefix}.we_gate"))
    u = jnp.einsum("gecd,edf->gecf", xe, qw(lp, qp, f"{prefix}.we_up"))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("gecf,efd->gecd", h, qw(lp, qp, f"{prefix}.we_down"))
    y = jnp.einsum("gnec,gecd->gnd", combine.astype(x.dtype), ye)

    if cfg.moe.shared_expert:
        y = y + mlp_apply(lp, qp, cfg, x, prefix=f"{prefix}.shared")
        return y.reshape(B, S, D)
    return y.reshape(B, S, D)


# ------------------------------------------------------------------ mamba
def init_mamba(key, cfg: ModelConfig, prefix: str, n_layers: int, dtype
               ) -> tuple[dict, dict]:
    """Jamba's Mamba-1 mixer (HF `JambaMambaMixer`): in_proj, a causal
    depthwise conv with bias, x_proj to (dt, B, C) with an RMSNorm on
    each, dt_proj with bias, and out_proj; no projection biases."""
    D = cfg.d_model
    mc = cfg.mamba
    Di = mc.expand * D
    dtr = mc.dt_rank or D // 16
    N = mc.d_state
    ks = jax.random.split(key, 6)
    L = (n_layers,) if n_layers else ()
    lax_ = ("layers",) if n_layers else ()
    p = {
        f"{prefix}.in_proj": jax.random.normal(ks[0], L + (D, 2 * Di), dtype) * D ** -0.5,
        f"{prefix}.conv_w": jax.random.normal(ks[1], L + (mc.d_conv, Di), dtype) * 0.1,
        f"{prefix}.conv_b": jnp.zeros(L + (Di,), dtype),
        f"{prefix}.x_proj": jax.random.normal(ks[2], L + (Di, dtr + 2 * N), dtype) * Di ** -0.5,
        f"{prefix}.dt_norm": jnp.ones(L + (dtr,), jnp.float32),
        f"{prefix}.b_norm": jnp.ones(L + (N,), jnp.float32),
        f"{prefix}.c_norm": jnp.ones(L + (N,), jnp.float32),
        f"{prefix}.dt_proj": jax.random.normal(ks[3], L + (dtr, Di), dtype) * dtr ** -0.5,
        f"{prefix}.dt_bias": jnp.zeros(L + (Di,), dtype),
        f"{prefix}.A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
            L + (Di, N)).astype(jnp.float32) * 1.0,
        f"{prefix}.D": jnp.ones(L + (Di,), jnp.float32),
        f"{prefix}.out_proj": jax.random.normal(ks[4], L + (Di, D), dtype) * Di ** -0.5,
    }
    axes = {
        f"{prefix}.in_proj": lax_ + ("embed", "mamba_inner2"),
        f"{prefix}.conv_w": lax_ + ("conv_k", "mamba_inner"),
        f"{prefix}.conv_b": lax_ + ("mamba_inner",),
        f"{prefix}.x_proj": lax_ + ("mamba_inner", "mamba_lowrank"),
        f"{prefix}.dt_norm": lax_ + ("mamba_lowrank_dt",),
        f"{prefix}.b_norm": lax_ + ("mamba_state",),
        f"{prefix}.c_norm": lax_ + ("mamba_state",),
        f"{prefix}.dt_proj": lax_ + ("mamba_lowrank_dt", "mamba_inner"),
        f"{prefix}.dt_bias": lax_ + ("mamba_inner",),
        f"{prefix}.A_log": lax_ + ("mamba_inner", "mamba_state"),
        f"{prefix}.D": lax_ + ("mamba_inner",),
        f"{prefix}.out_proj": lax_ + ("mamba_inner", "embed"),
    }
    return p, axes


def mamba_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
                prefix: str, state: Optional[tuple] = None,
                shapes: Optional[LayerShapes] = None):
    """Jamba's Mamba-1 mixer (HF `JambaMambaMixer`):

        x_in, z = in_proj(x)
        u       = silu(conv_b + causal depthwise conv(x_in))
        dt, B, C = rmsnorm(.) of each part of x_proj(u)
        dt      = softplus(dt_proj(dt) + dt_bias)
        h_t     = exp(dt_t A) h_{t-1} + dt_t u_t B_t ;  y_t = <h_t, C_t>
        out     = out_proj((y + D u) * silu(z))

    state = (h (B,Di,N), conv (B,K-1,Di)) for decode: the last K-1 conv
    inputs (before the bias). Di comes from `shapes` (pruned subnets keep
    fewer inner channels); dt_rank and d_state are never pruned. The
    discretisation, state update and readout run under the `ssm_update`
    name scope. Returns (out, new_state)."""
    B, S, D = x.shape
    mc = cfg.mamba
    shapes = shapes or LayerShapes.from_config(cfg)
    Di = shapes.mamba_inner
    N = mc.d_state
    Kc = mc.d_conv
    eps = cfg.norm_eps

    xi = dense_proj(x, lp, qp, f"{prefix}.in_proj_x")   # (B, S, Di)
    z = dense_proj(x, lp, qp, f"{prefix}.in_proj_z")

    with jax.named_scope("pointwise"):
        conv_w = lp[f"{prefix}.conv_w"].astype(jnp.float32)   # (K, Di)
        if state is None:
            pad = jnp.zeros((B, Kc - 1, Di), xi.dtype)
            xpad = jnp.concatenate([pad, xi], axis=1)
            new_conv = xpad[:, -(Kc - 1):] if Kc > 1 else pad
        else:
            h_prev, conv_prev = state
            xpad = jnp.concatenate([conv_prev.astype(xi.dtype), xi], axis=1)
            new_conv = xpad[:, -(Kc - 1):] if Kc > 1 else conv_prev
        xc = sum(xpad[:, i:i + S].astype(jnp.float32) * conv_w[i]
                 for i in range(Kc))
        xc = xc + lp[f"{prefix}.conv_b"].astype(jnp.float32)
        xc = jax.nn.silu(xc).astype(x.dtype)

    proj = dense_proj(xc, lp, qp, f"{prefix}.x_proj")
    dtr = (cfg.mamba.dt_rank or D // 16)
    dt_low, Bc, Cc = jnp.split(proj, [dtr, dtr + N], axis=-1)
    dt_low = rmsnorm(dt_low, lp[f"{prefix}.dt_norm"], eps)
    Bc = rmsnorm(Bc, lp[f"{prefix}.b_norm"], eps)
    Cc = rmsnorm(Cc, lp[f"{prefix}.c_norm"], eps)
    dt_raw = dense_proj(dt_low, lp, qp, f"{prefix}.dt_proj")

    with jax.named_scope("ssm_update"):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + lp[f"{prefix}.dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lp[f"{prefix}.A_log"].astype(jnp.float32))  # (Di, N)
        h0 = jnp.zeros((B, Di, N), jnp.float32) if state is None \
            else state[0]
        y, h_last = Kops.selective_scan_op(xc, dt, Bc, Cc, A, h0,
                                           chunk=mc.chunk)
        y = y + lp[f"{prefix}.D"].astype(jnp.float32) * xc.astype(jnp.float32)
    with jax.named_scope("pointwise"):
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        y = qa(y, qp, f"{prefix}.mamba_out.aq")
    out = dense_proj(y, lp, qp, f"{prefix}.out_proj")
    return out, (h_last, new_conv)


# ------------------------------------------------------------------ rwkv6
def init_rwkv(key, cfg: ModelConfig, prefix: str, n_layers: int, dtype
              ) -> tuple[dict, dict]:
    D, F = cfg.d_model, cfg.d_ff
    rc = cfg.rwkv
    H = D // rc.head_size
    R = rc.decay_lora
    ks = jax.random.split(key, 10)
    L = (n_layers,) if n_layers else ()
    lax_ = ("layers",) if n_layers else ()
    std = D ** -0.5
    p = {
        # time-mix
        f"{prefix}.mu": jax.random.uniform(ks[0], L + (5, D), dtype),
        f"{prefix}.wr": jax.random.normal(ks[1], L + (D, D), dtype) * std,
        f"{prefix}.wk": jax.random.normal(ks[2], L + (D, D), dtype) * std,
        f"{prefix}.wv": jax.random.normal(ks[3], L + (D, D), dtype) * std,
        f"{prefix}.wg": jax.random.normal(ks[4], L + (D, D), dtype) * std,
        f"{prefix}.wo": jax.random.normal(ks[5], L + (D, D), dtype) * std,
        f"{prefix}.decay_w1": jax.random.normal(ks[6], L + (D, R), dtype) * std,
        f"{prefix}.decay_w2": jax.random.normal(ks[7], L + (R, D), dtype) * R ** -0.5,
        f"{prefix}.decay_w0": jnp.full(L + (D,), -1.0, jnp.float32),
        f"{prefix}.u": jnp.zeros(L + (D,), jnp.float32),   # time_first
        f"{prefix}.lnx_scale": jnp.ones(L + (D,), jnp.float32),
        f"{prefix}.lnx_bias": jnp.zeros(L + (D,), jnp.float32),
        # channel-mix
        f"{prefix}.cm_mu": jax.random.uniform(ks[8], L + (2, D), dtype),
        f"{prefix}.cm_k": jax.random.normal(ks[9], L + (D, F), dtype) * std,
        f"{prefix}.cm_v": jax.random.normal(ks[0], L + (F, D), dtype) * F ** -0.5,
        f"{prefix}.cm_r": jax.random.normal(ks[1], L + (D, D), dtype) * std,
    }
    axes = {
        f"{prefix}.mu": lax_ + ("mix5", "embed"),
        f"{prefix}.wr": lax_ + ("embed", "rwkv_heads"),
        f"{prefix}.wk": lax_ + ("embed", "rwkv_heads"),
        f"{prefix}.wv": lax_ + ("embed", "rwkv_heads"),
        f"{prefix}.wg": lax_ + ("embed", "rwkv_heads"),
        f"{prefix}.wo": lax_ + ("rwkv_heads", "embed"),
        f"{prefix}.decay_w1": lax_ + ("embed", "lora"),
        f"{prefix}.decay_w2": lax_ + ("lora", "rwkv_heads"),
        f"{prefix}.decay_w0": lax_ + ("rwkv_heads",),
        f"{prefix}.u": lax_ + ("rwkv_heads",),
        f"{prefix}.lnx_scale": lax_ + ("rwkv_heads",),
        f"{prefix}.lnx_bias": lax_ + ("rwkv_heads",),
        f"{prefix}.cm_mu": lax_ + ("mix2", "embed"),
        f"{prefix}.cm_k": lax_ + ("embed", "rwkv_ffn"),
        f"{prefix}.cm_v": lax_ + ("rwkv_ffn", "embed"),
        f"{prefix}.cm_r": lax_ + ("embed", "rwkv_heads"),
    }
    return p, axes


def _token_shift(x, last: Optional[jax.Array]):
    """xs[t] = x[t-1]; xs[0] = last (or 0)."""
    B, S, D = x.shape
    if S == 1:
        prev = jnp.zeros((B, 1, D), x.dtype) if last is None \
            else last[:, None].astype(x.dtype)
        return prev
    head = jnp.zeros((B, 1, D), x.dtype) if last is None \
        else last[:, None].astype(x.dtype)
    return jnp.concatenate([head, x[:, :-1]], axis=1)


def _wkv_scan(r, k, v, w, u, s0, chunk: int = 64):
    """WKV recurrence, chunked two-level scan (exact; per-token math).

    r,k,v,w: (B, S, H, dh); u: (H, dh); s0: (B, H, dh, dh).
    y_t = r_t @ (S_t + u * k_t^T v_t); S_{t+1} = diag(w_t) S_t + k_t^T v_t.

    The outer scan carries state across chunks with a checkpointed body, so
    the backward keeps one (B,H,dh,dh) state per *chunk* instead of per
    token (a ~chunk x HBM reduction; per-token residuals measured at
    tens of GB for 4k-seq full configs). The TPU-optimized path would be a
    chunked Pallas kernel (DESIGN.md); this is the reference + dry-run path.
    """
    B, S, H, dh = r.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    nch = S // C

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp   # (B, H, dh)
        kv = k_t[..., :, None] * v_t[..., None, :]        # (B,H,dh,dh)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, ..., None] * kv)
        s_new = w_t[..., None] * s + kv
        return s_new, y

    def chunk_fn(s, inp):
        rc, kc, vc, wc = inp       # (C, B, H, dh)
        return jax.lax.scan(step, s, (rc, kc, vc, wc))

    def chunked(t):
        t = jnp.moveaxis(t, 1, 0).astype(jnp.float32)     # (S, B, H, dh)
        return t.reshape(nch, C, B, H, dh)

    s_last, ys = jax.lax.scan(
        jax.checkpoint(chunk_fn), s0,
        (chunked(r), chunked(k), chunked(v), chunked(w)))
    ys = ys.reshape(S, B, H, dh)
    return jnp.moveaxis(ys, 0, 1), s_last   # (B,S,H,dh)


def rwkv_timemix_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
                       prefix: str, state: Optional[tuple] = None,
                       shapes: Optional[LayerShapes] = None):
    """RWKV6 (Finch) time-mix with data-dependent decay.

    state = (shift_last (B,D), wkv_state (B,H,dh,dh)); H comes from
    `shapes` (pruned subnets keep fewer heads). Returns (out, state).
    """
    B, S, D = x.shape
    rc = cfg.rwkv
    dh = rc.head_size
    shapes = shapes or LayerShapes.from_config(cfg)
    H = shapes.rwkv_heads
    last = state[0] if state is not None else None
    xs = _token_shift(x, last)
    mu = lp[f"{prefix}.mu"].astype(jnp.float32)  # (5, D)
    dx = (xs - x).astype(jnp.float32)
    x32 = x.astype(jnp.float32)

    def mixed(i):
        return (x32 + dx * mu[i]).astype(x.dtype)

    r = dense_proj(mixed(0), lp, qp, f"{prefix}.wr").reshape(B, S, H, dh)
    k = dense_proj(mixed(1), lp, qp, f"{prefix}.wk").reshape(B, S, H, dh)
    v = dense_proj(mixed(2), lp, qp, f"{prefix}.wv").reshape(B, S, H, dh)
    g = jax.nn.silu(dense_proj(mixed(3), lp, qp, f"{prefix}.wg")
                    .astype(jnp.float32))
    # data-dependent decay (LoRA)
    dd = jnp.tanh(dense_proj(mixed(4), lp, qp, f"{prefix}.decay_w1")
                  .astype(jnp.float32))
    dd = dense_proj(dd, lp, qp, f"{prefix}.decay_w2").astype(jnp.float32)
    logw = -jnp.exp(jnp.clip(
        lp[f"{prefix}.decay_w0"].astype(jnp.float32) + dd, -8.0, 4.0))
    w = jnp.exp(logw).reshape(B, S, H, dh)
    u = lp[f"{prefix}.u"].astype(jnp.float32).reshape(H, dh)

    s0 = jnp.zeros((B, H, dh, dh), jnp.float32) if state is None \
        else state[1]
    y, s_last = _wkv_scan(r, k, v, w, u, s0, chunk=rc.chunk)
    y = groupnorm_heads(y.reshape(B, S, H * dh).astype(x.dtype),
                        lp[f"{prefix}.lnx_scale"], lp[f"{prefix}.lnx_bias"],
                        H, cfg.norm_eps)
    y = (y.astype(jnp.float32) * g).astype(x.dtype)
    y = qa(y, qp, f"{prefix}.tm_out.aq")
    out = dense_proj(y, lp, qp, f"{prefix}.wo")
    return out, (x[:, -1].astype(jnp.float32), s_last)


def rwkv_chanmix_apply(lp: dict, qp: Optional[dict], cfg: ModelConfig, x, *,
                       prefix: str, state: Optional[jax.Array] = None):
    """RWKV channel-mix FFN. state = shift_last (B, D)."""
    xs = _token_shift(x, state)
    mu = lp[f"{prefix}.cm_mu"].astype(jnp.float32)
    dx = (xs - x).astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    xk = (x32 + dx * mu[0]).astype(x.dtype)
    xr = (x32 + dx * mu[1]).astype(x.dtype)
    k = jnp.square(jax.nn.relu(dense_proj(xk, lp, qp, f"{prefix}.cm_k")
                               .astype(jnp.float32))).astype(x.dtype)
    k = qa(k, qp, f"{prefix}.cm_act.aq")
    val = dense_proj(k, lp, qp, f"{prefix}.cm_v")
    r = jax.nn.sigmoid(dense_proj(xr, lp, qp, f"{prefix}.cm_r")
                       .astype(jnp.float32))
    out = (val.astype(jnp.float32) * r).astype(x.dtype)
    return out, x[:, -1].astype(jnp.float32)
