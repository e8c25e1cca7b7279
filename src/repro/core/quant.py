"""Learnable quantization with parameters (d, q_m, t) — paper §3, Eqs (1)-(6).

The quantizer maps a tensor x through a nonlinear clip

    x~ = sgn(x) * clip_{q_m}^t(|x|),   clip_{q_m}^t(a) = a^t       if a <= q_m
                                                         (q_m)^t   if a >  q_m
then symmetric uniform quantization

    x_Q = d * round(x~ / d)                                         (Eq 2)

The bit width is a *derived* quantity (Eq 3):

    b = log2((q_m)^t / d + 1) + 1

Gradients of x_Q w.r.t. (d, t, q_m) follow the straight-through estimator
(Eqs 4-6); the gradient w.r.t. x is STE identity inside the clip range and
rescaled by the clip boundary outside (standard PACT-style behaviour).

All functions are pure jnp and jit/vmap/pjit friendly. The Pallas-fused
version lives in `repro.kernels`; this module is the mathematical source of
truth (the kernels' ref oracle imports from here).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

# Numerical guards: t and q_m pass through powers/logs.
_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Per-layer learnable quantization parameters (pytree).

    Each field is a scalar (per-tensor quantization, as in the paper) held in
    float32 regardless of the activation dtype so that tiny gradient updates
    are not lost to bf16 rounding.
    """

    d: jax.Array    # quantization step size  (> 0)
    q_m: jax.Array  # clip maximum            (> 0)
    t: jax.Array    # shaping exponent        (> 0), t=1 -> uniform

    def tree_flatten(self):
        return (self.d, self.q_m, self.t), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    QuantParams, QuantParams.tree_flatten, QuantParams.tree_unflatten
)


def init_quant_params(
    w: jax.Array | None = None,
    *,
    q_m: float | jax.Array | None = None,
    bits: float = 32.0,
    t: float = 1.0,
) -> QuantParams:
    """Paper Appendix C initialization: t = 1, q_m = max|W|, d chosen so the
    derived bit width equals `bits` (32 for CNNs-from-scratch, 8 for BERT)."""
    if q_m is None:
        if w is None:
            raise ValueError("need either a weight tensor or explicit q_m")
        q_m = jnp.maximum(jnp.max(jnp.abs(w)).astype(jnp.float32), 1e-3)
    q_m = jnp.asarray(q_m, jnp.float32)
    t_arr = jnp.asarray(t, jnp.float32)
    d = step_size_for_bits(q_m, t_arr, jnp.asarray(bits, jnp.float32))
    return QuantParams(d=d, q_m=q_m, t=t_arr)


def bit_width(d: jax.Array, q_m: jax.Array, t: jax.Array) -> jax.Array:
    """Eq (3): b = log2((q_m)^t / d + 1) + 1."""
    peak = jnp.power(jnp.maximum(q_m, _EPS), t)
    return jnp.log2(peak / jnp.maximum(d, _EPS) + 1.0) + 1.0


def step_size_for_bits(q_m: jax.Array, t: jax.Array, bits: jax.Array) -> jax.Array:
    """Invert Eq (3): the d that realizes a given bit width."""
    peak = jnp.power(jnp.maximum(q_m, _EPS), t)
    return peak / (jnp.exp2(bits - 1.0) - 1.0)


def step_size_bounds(
    q_m: jax.Array, t: jax.Array, b_l: jax.Array, b_u: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """[d_min, d_max] such that b in [b_l, b_u] (Alg 3 line 3).

    b is decreasing in d, so b <= b_u  <=>  d >= d(b_u)  and
    b >= b_l  <=>  d <= d(b_l)."""
    d_min = step_size_for_bits(q_m, t, b_u)
    d_max = step_size_for_bits(q_m, t, b_l)
    return d_min, d_max


def pos_pow(base: jax.Array, t: jax.Array) -> jax.Array:
    """base ** t for base > 0, as exp(t * log(base)) — the form the Pallas
    kernels use. `jnp.power` with a traced float exponent lowers to XLA's
    general pow (negative bases, integer exponents); fused into the
    operand of a weight GEMM it made the TPU compiler take 10-20x longer
    on every fake-quantized program (a 3-layer internlm2-1.8b prefill
    compiled for v5e in 46 s, 3.8 s with this form)."""
    return jnp.exp(t * jnp.log(base))


def clip_qmt(x_abs: jax.Array, q_m: jax.Array, t: jax.Array,
             power=pos_pow) -> jax.Array:
    """clip_{q_m}^t(|x|) of Eq (13) — the nonlinear clipped magnitude.
    `power(base, t)` raises the clamped magnitude: `pos_pow` for the
    quantizers, `jnp.power` for QASSO's site statistics
    (`QASSO._site_stats_chunked` says why)."""
    q_m = jnp.maximum(q_m, _EPS)
    a = jnp.minimum(x_abs, q_m)
    return power(jnp.maximum(a, _EPS), t) * (x_abs > 0)


def residual(x_abs: jax.Array, d: jax.Array, q_m: jax.Array, t: jax.Array,
             power=pos_pow) -> jax.Array:
    """R(x) of Eq (14): round(x~/d) - x~/d for the clipped magnitude."""
    xt = clip_qmt(x_abs, q_m, t, power)
    r = xt / jnp.maximum(d, _EPS)
    return jnp.round(r) - r


def _fake_quant_fwd_math(x, d, q_m, t):
    """Shared forward math (Eqs 1-2). Returns x_Q with the dtype of x."""
    d32 = jnp.maximum(d.astype(jnp.float32), _EPS)
    sign = jnp.sign(x).astype(jnp.float32)
    xt = clip_qmt(jnp.abs(x).astype(jnp.float32), q_m.astype(jnp.float32),
                  t.astype(jnp.float32))
    xq = d32 * jnp.round(xt / d32) * sign
    return xq.astype(x.dtype)


@jax.custom_vjp
def fake_quant(x: jax.Array, d: jax.Array, q_m: jax.Array, t: jax.Array) -> jax.Array:
    """Differentiable quantize-dequantize with learnable (d, q_m, t).

    Forward: Eqs (1)-(2). Backward: STE for x, Eqs (4)-(6) for the scalars.
    """
    return _fake_quant_fwd_math(x, d, q_m, t)


def _fake_quant_fwd(x, d, q_m, t):
    y = _fake_quant_fwd_math(x, d, q_m, t)
    return y, (x, d, q_m, t)


def _fake_quant_bwd(res, g):
    x, d, q_m, t = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    d32 = jnp.maximum(d.astype(jnp.float32), _EPS)
    qm32 = jnp.maximum(q_m.astype(jnp.float32), _EPS)
    t32 = t.astype(jnp.float32)

    ax = jnp.abs(x32)
    sign = jnp.sign(x32)
    inside = ax <= qm32
    safe_ax = jnp.maximum(ax, _EPS)

    # --- dL/dx: STE. Inside the clip: d x_Q/dx ~ d x~/dx = t*|x|^{t-1}
    # treated as 1 by the STE (the paper's STE passes the gradient through
    # the round *and* the power; outside the clip the gradient is 0).
    dx = jnp.where(inside, g32, 0.0).astype(x.dtype)

    # --- Eq (4): dx_Q/dd = sgn(x) * (round(v) - v), v = clip^t/d.
    v = clip_qmt(ax, qm32, t32) / d32
    dd_elem = sign * (jnp.round(v) - v)
    dd = jnp.sum(g32 * dd_elem).astype(jnp.float32)

    # --- Eq (5): dx_Q/dt = sgn(x) * clip^t * log(clip_base)
    base = jnp.where(inside, safe_ax, qm32)
    dt_elem = sign * pos_pow(base, t32) * jnp.log(base)
    dt = jnp.sum(g32 * dt_elem).astype(jnp.float32)

    # --- Eq (6): dx_Q/dq_m = 0 inside, sgn(x)*t*q_m^{t-1} outside.
    dqm_elem = jnp.where(inside, 0.0, sign * t32 * jnp.power(qm32, t32 - 1.0))
    dqm = jnp.sum(g32 * dqm_elem).astype(jnp.float32)

    return dx, dd.reshape(d.shape), dqm.reshape(q_m.shape), dt.reshape(t.shape)


fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def quantize_int(x: jax.Array, qp: QuantParams,
                 bits: float | jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Deployment-path quantization: integer codes + scale.

    Returns (codes int8/int16/int32 depending on derived bits, scale d).
    Codes satisfy x_Q = codes * d (on the nonlinearly-mapped magnitude).

    Codes are clamped to the symmetric range of the ceil(bits)-wide
    container, ±(2^(ceil(b)-1)-1): at the bit-constraint boundary (d
    projected to exactly the layerwise b_u, then q_m nudged by a later
    step) `round(xt/d)` can land on 2^(b-1) — e.g. 128 at 8 bits — which
    would wrap negative in the narrow integer cast downstream. `bits`
    overrides the derived width when the caller has already fixed the
    container (default: Eq 3 on `qp`)."""
    d32 = jnp.maximum(qp.d.astype(jnp.float32), _EPS)
    sign = jnp.sign(x).astype(jnp.float32)
    xt = clip_qmt(jnp.abs(x).astype(jnp.float32), qp.q_m, qp.t)
    codes = jnp.round(xt / d32) * sign
    b = bit_width(qp.d, qp.q_m, qp.t) if bits is None \
        else jnp.asarray(bits, jnp.float32)
    cmax = jnp.exp2(jnp.ceil(b) - 1.0) - 1.0
    codes = jnp.clip(codes, -cmax, cmax)
    return codes, d32


def dequantize_int(codes: jax.Array, d: jax.Array,
                   out_dtype=jnp.float32) -> jax.Array:
    """Reconstruct the effective weight x_Q = codes * d.

    Note: per Eqs (1)-(2) the quantized value x_Q lives in the *shaped*
    domain (the t-companding is part of the learned effective weight and is
    never inverted at inference) — so dequantization is a single multiply."""
    return (codes * d).astype(out_dtype)


def storage_bits(qp: QuantParams) -> jax.Array:
    """Integer bits needed to store codes of this quantizer (ceil of Eq 3)."""
    return jnp.ceil(bit_width(qp.d, qp.q_m, qp.t))


# ------------------------------------------------------- sub-byte packing
# Storage widths the packed serving path realizes. A site whose learned
# width lands between two entries rounds up to the next one (ceil 5..8 all
# store at 8); widths above 8 keep their unpacked int16/int32 container.
PACKED_STORAGE_BITS = (2, 3, 4, 8)


def packed_storage_bits(bits: float) -> int | None:
    """Packed container width for a learned bit width, or None if the
    codes need more than 8 bits (stay on the unpacked int16/int32 path)."""
    nb = int(jnp.ceil(jnp.asarray(bits, jnp.float32)))
    for cand in PACKED_STORAGE_BITS:
        if nb <= cand:
            return cand
    return None


def _codes_per_word(bits: int) -> int:
    if not 2 <= int(bits) <= 8:
        raise ValueError(f"packed bits must be in [2, 8], got {bits}")
    return 32 // int(bits)


def pack_codes(codes: jax.Array, bits: int, *, axis: int = 0) -> jax.Array:
    """Bit-pack signed integer codes into an int32 word stream.

    Each 32-bit word holds ``32 // bits`` codes (16/10/8/4 for bits
    2/3/4/8) as ``bits``-wide two's-complement fields, least-significant
    field first, packed along `axis` (the reduction/K axis for weight
    matrices, so the per-column scale epilogue is untouched). A trailing
    partial word is zero-padded — zero codes dequantize to exact zeros,
    so the padding is inert in any matmul whose LHS is zero-padded to
    match. Codes must already fit ±(2^(bits-1)-1) (`quantize_int` clamps
    to exactly that range)."""
    bits = int(bits)
    cpw = _codes_per_word(bits)
    c = jnp.moveaxis(jnp.asarray(codes), axis, 0).astype(jnp.int32)
    pad = (-c.shape[0]) % cpw
    if pad:
        c = jnp.pad(c, ((0, pad),) + ((0, 0),) * (c.ndim - 1))
    mask = (1 << bits) - 1
    c = (c & mask).reshape((c.shape[0] // cpw, cpw) + c.shape[1:])
    shifts = (jnp.arange(cpw, dtype=jnp.int32) * bits).reshape(
        (1, cpw) + (1,) * (c.ndim - 2))
    # fields are disjoint, so the sum is a bitwise OR (int32 wraparound on
    # the sign bit of the top field is the intended two's-complement word)
    words = jnp.sum(c << shifts, axis=1, dtype=jnp.int32)
    return jnp.moveaxis(words, 0, axis)


def unpack_codes(packed: jax.Array, bits: int, size: int, *,
                 axis: int = 0) -> jax.Array:
    """Invert `pack_codes`: int32 words -> sign-extended int32 codes.

    `size` is the unpadded code count along `axis` (the word stream holds
    ceil(size / (32//bits)) words; the zero-filled tail is sliced off)."""
    bits = int(bits)
    cpw = _codes_per_word(bits)
    w = jnp.moveaxis(jnp.asarray(packed, jnp.int32), axis, 0)
    shifts = (jnp.arange(cpw, dtype=jnp.int32) * bits).reshape(
        (1, cpw) + (1,) * (w.ndim - 1))
    mask = (1 << bits) - 1
    vals = (w[:, None] >> shifts) & mask
    sgn = 1 << (bits - 1)
    vals = (vals ^ sgn) - sgn   # sign-extend the bits-wide field
    out = vals.reshape((w.shape[0] * cpw,) + w.shape[1:])[:size]
    return jnp.moveaxis(out, 0, axis)


def code_plane(words: jax.Array, bits: int, field: int) -> jax.Array:
    """Field `field` of every `pack_codes` word, sign-extended: the codes
    at K rows `r * (32 // bits) + field` of a K-packed stream, in the
    words' own shape. Two shifts: the field's top bit to bit 31, then an
    arithmetic shift back down (no mask, no interleave), so a kernel
    decodes a word tile one whole plane at a time.
    `unpack_codes(w)[r * cpw + i] == code_plane(w, bits, i)[r]`."""
    bits, field = int(bits), int(field)
    assert 0 <= field < _codes_per_word(bits), (bits, field)
    return (words << (32 - bits * (field + 1))) >> (32 - bits)


# Storage widths the paged KV arena can hold codes at (DESIGN.md §4.11).
# Weight containers pack along the GEMM K axis into int32 words
# (`pack_codes`); KV pages instead pack along d_head into int8 bytes —
# the page is the streaming unit and a byte stream keeps the in-kernel
# nibble unpack a shift pair instead of a word-field walk.
KV_STORAGE_BITS = (4, 8)


def kv_quant_encode(x: jax.Array, bits: int) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric absmax quantization for KV-cache pages.

    x: (..., dh) float rows (one K or V head-row per leading index).
    Returns (codes int8, scale f32 (...,)): scale = absmax / qmax per
    row so every write is independent (no page rescaling when a new row
    lands — the property that makes incremental decode writes exact).
    All-zero rows encode to codes 0 / scale 0 and decode to exact zeros,
    preserving the arena zero-init invariant through a quantize-dequantize
    round trip. bits=4 nibble-packs code pairs along the last axis
    ((..., dh//2) bytes, low nibble first)."""
    bits = int(bits)
    if bits not in KV_STORAGE_BITS:
        raise ValueError(f"kv bits must be one of {KV_STORAGE_BITS}, "
                         f"got {bits}")
    qmax = (1 << (bits - 1)) - 1
    x32 = jnp.asarray(x).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1) / qmax
    d = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round(x32 / d[..., None]),
                     -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        if x32.shape[-1] % 2:
            raise ValueError(f"kv bits=4 packs code pairs; d_head="
                             f"{x32.shape[-1]} must be even")
        codes = (codes[..., 0::2] & 0xF) | ((codes[..., 1::2] & 0xF) << 4)
    return codes.astype(jnp.int8), scale.astype(jnp.float32)


def kv_quant_decode(codes: jax.Array, scale: jax.Array, bits: int
                    ) -> jax.Array:
    """Invert `kv_quant_encode`: int8 codes + per-row scales -> f32 rows.

    Exact for zero rows (scale 0 times codes 0) and idempotent under
    re-encode at the same bits (round(c*d/d) == c), so a gather ->
    compute -> re-encode scatter of untouched rows is a no-op."""
    bits = int(bits)
    w = jnp.asarray(codes).astype(jnp.int32)
    if bits == 4:
        lo = (w << 28) >> 28          # sign-extend the low nibble
        hi = (w << 24) >> 28          # arithmetic shift: high nibble
        w = jnp.stack([lo, hi], axis=-1).reshape(
            w.shape[:-1] + (w.shape[-1] * 2,))
    elif bits != 8:
        raise ValueError(f"kv bits must be one of {KV_STORAGE_BITS}, "
                         f"got {bits}")
    return w.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def tree_bit_widths(qparams: dict[str, QuantParams]) -> dict[str, jax.Array]:
    return {k: bit_width(v.d, v.q_m, v.t) for k, v in qparams.items()}


def project_step_size(qp: QuantParams, b_l: float | jax.Array,
                      b_u: float | jax.Array) -> QuantParams:
    """PPSG projection (Alg 3 lines 3-4): clamp d into [d_min, d_max].

    Only d is projected — q_m and t are left untouched (paper §5.1: their
    exponential gradient terms make abrupt projection destabilizing)."""
    d_min, d_max = step_size_bounds(qp.q_m, qp.t,
                                    jnp.asarray(b_l, jnp.float32),
                                    jnp.asarray(b_u, jnp.float32))
    return QuantParams(d=jnp.clip(qp.d, d_min, d_max), q_m=qp.q_m, t=qp.t)


def positivity_guard(qp: QuantParams) -> QuantParams:
    """Keep the parameterization in its valid open domain after an SGD step."""
    return QuantParams(
        d=jnp.maximum(qp.d, 1e-8),
        q_m=jnp.maximum(qp.q_m, 1e-6),
        t=jnp.clip(qp.t, 0.05, 4.0),
    )
