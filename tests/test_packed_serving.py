"""Sub-byte packed serving: pack/unpack exactness, the unpack-dequant GEMM
epilogue, and packed-vs-unpacked decode parity.

The contract: `unpack_codes(pack_codes(c, b), b) == c` exactly for every
storage width, so a packed decode runs the *same* dequantized weights as
the unpacked int8 path — logits agree to float tolerance and greedy
tokens bit-for-bit, while the packed containers occupy `b/8` of the int8
bytes (the ISSUE's ≤0.55x-at-4-bit acceptance). Also pins the satellite
fixes: `quantize_int` boundary clamping, the `compression_report`
sparsity-0.0 line, and `mean_storage_bits`.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import quant as Q
from repro.core.subnet import (compress_lm, compression_report,
                               prepare_serving, residual_qparams,
                               servable_params, tree_bytes)
from repro.kernels import dispatch, gemm_core, ops
from repro.kernels.ref import packed_quant_matmul_ref, quant_matmul_ref
from repro.models.transformer import LM

BACKENDS = ("xla-ref", "pallas-interpret")


# ------------------------------------------------------------ pack/unpack
@pytest.mark.parametrize("bits", list(range(2, 9)))
def test_pack_unpack_roundtrip_exact(bits):
    """Round-trip is exact for every width 2-8, negative codes included,
    at word-aligned and non-aligned lengths, 1-D through stacked 3-D."""
    hi = 2 ** (bits - 1) - 1
    rng = np.random.RandomState(bits)
    for n in (1, 5, 31, 32, 33, 160):
        c = rng.randint(-hi, hi + 1, size=(n,)).astype(np.int32)
        u = np.asarray(Q.unpack_codes(Q.pack_codes(jnp.asarray(c), bits),
                                      bits, n))
        np.testing.assert_array_equal(u, c)
    # K-packed 2-D and scan-stacked 3-D (the weight layouts serving uses)
    for shape in ((13, 5), (3, 11, 4)):
        c = rng.randint(-hi, hi + 1, size=shape).astype(np.int32)
        p = Q.pack_codes(jnp.asarray(c), bits, axis=-2)
        assert p.dtype == jnp.int32
        cpw = 32 // bits
        assert p.shape[-2] == -(-shape[-2] // cpw)   # ceil(K / cpw) words
        u = np.asarray(Q.unpack_codes(p, bits, shape[-2], axis=-2))
        np.testing.assert_array_equal(u, c)


def test_pack_codes_extreme_values_sign_extend():
    """The full symmetric range ±(2^(b-1)-1) survives packing — the sign
    bit of every field must extend, not zero-fill."""
    for bits in (2, 3, 4, 8):
        hi = 2 ** (bits - 1) - 1
        c = jnp.asarray([-hi, -1, 0, 1, hi], jnp.int32)
        u = np.asarray(Q.unpack_codes(Q.pack_codes(c, bits), bits, 5))
        np.testing.assert_array_equal(u, np.asarray(c))


def test_packed_storage_bits_rounding():
    assert Q.packed_storage_bits(1.7) == 2
    assert Q.packed_storage_bits(2.0) == 2
    assert Q.packed_storage_bits(2.3) == 3
    assert Q.packed_storage_bits(4.0) == 4
    assert Q.packed_storage_bits(4.8) == 8
    assert Q.packed_storage_bits(8.0) == 8
    assert Q.packed_storage_bits(8.2) is None   # needs int16, unpacked


# --------------------------------------------------------- GEMM epilogue
# ragged K (a partial last word) and N, at decode (M = 1, 28: the
# artifact's 28 slots) and prefill (M = 130, 300) row counts; each shape
# with f32 activations, and the new ones with bf16 too
PACKED_SHAPES = [(1, 1, 1), (3, 193, 17), (29, 31, 37), (130, 257, 131),
                 (28, 300, 260), (300, 1030, 131)]
PACKED_CASES = ([(mkn, jnp.float32) for mkn in PACKED_SHAPES]
                + [(mkn, jnp.bfloat16) for mkn in ((1, 1, 1), (3, 193, 17),
                                                   (28, 300, 260),
                                                   (300, 1030, 131))])


def _case_id(case):
    (m, k, n), x_dtype = case
    return f"{m}x{k}x{n}" + ("-bf16" if x_dtype == jnp.bfloat16 else "")


def _packed_case(bits, m, k, n, x_dtype, seed):
    hi = 2 ** (bits - 1) - 1
    rng = np.random.RandomState(seed)
    codes = rng.randint(-hi, hi + 1, size=(k, n)).astype(np.int8)
    scale = ((rng.rand(n) + 0.5) * (2.0 / max(hi, 1))).astype(np.float32)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32)).astype(x_dtype)
    return x, codes, scale, rng


def test_code_plane_is_unpack_codes_field():
    """The kernel's two-shift field plane reads the `pack_codes` format
    exactly as `unpack_codes` does, sign and all, for every width."""
    for bits in range(2, 9):
        hi, cpw = 2 ** (bits - 1) - 1, 32 // bits
        rng = np.random.RandomState(bits)
        c = rng.randint(-hi, hi + 1, size=(7 * cpw, 5)).astype(np.int32)
        c[0], c[1] = hi, -hi
        w = Q.pack_codes(jnp.asarray(c), bits, axis=0)
        for i in range(cpw):
            np.testing.assert_array_equal(
                np.asarray(Q.code_plane(w, bits, i)), c[i::cpw])


def _packed_gemm(x, packed, bits, scale, backend, rhs_ops=None, **kw):
    """The packed GEMM, its result in f32 (a bf16 output would round
    either side's last bit)."""
    if x.dtype == jnp.float32 and rhs_ops is None and not kw:
        return ops.packed_quant_matmul_op(x, packed, bits, scale,
                                          backend=backend)
    rhs_ops = rhs_ops or (gemm_core.unpack_dequant(bits, scale),)
    return gemm_core.gemm(x, packed, rhs_ops, backend=backend,
                          out_dtype=jnp.float32, **kw)


@pytest.mark.parametrize("case", PACKED_CASES, ids=_case_id)
@pytest.mark.parametrize("bits", [2, 3, 4, 8])
def test_unpack_dequant_backend_parity(bits, case):
    """Packed GEMM == unpacked dequant oracle on both backends, over
    ragged shapes (incl. bits=3, whose 10-codes-per-word stream aligns K
    blocks to 640 codes), with f32 and bf16 activations (the packed
    body's MXU operands follow x: bf16 planes for bf16 x)."""
    (m, k, n), x_dtype = case
    x, codes, scale, _ = _packed_case(bits, m, k, n, x_dtype,
                                      bits * 1009 + k * 11 + n)
    packed = Q.pack_codes(jnp.asarray(codes), bits, axis=0)
    x32 = x.astype(jnp.float32)
    want = quant_matmul_ref(x32, jnp.asarray(codes), jnp.asarray(scale))
    ref = packed_quant_matmul_ref(x32, packed, bits, jnp.asarray(scale))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for backend in BACKENDS:
        got = _packed_gemm(x, packed, bits, jnp.asarray(scale), backend)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_unpack_dequant_composes_with_col_mask():
    """Epilogue order: unpack-dequant decodes the raw words first; the
    scale and later COL ops (`col_mask`) multiply the result columns, at
    the last K step where K spans several blocks (DESIGN.md §4.8). Every
    width, f32 and bf16 activations, K in one block and in several."""
    cases = [((5, 31, 37), None), ((28, 2100, 260), (32, 128, 1024))]
    for bits in (2, 3, 4, 8):
        cpw = 32 // bits
        for (m, k, n), blocks in cases:
            for x_dtype in (jnp.float32, jnp.bfloat16):
                x, codes, scale, rng = _packed_case(bits, m, k, n, x_dtype,
                                                    bits)
                mask = (rng.rand(n) > 0.4).astype(np.float32)
                packed = Q.pack_codes(jnp.asarray(codes), bits, axis=0)
                rhs_ops = (gemm_core.unpack_dequant(bits,
                                                    jnp.asarray(scale)),
                           gemm_core.col_mask(jnp.asarray(mask)))
                if blocks is not None:     # K really takes several blocks
                    _, _, bk, bkw = gemm_core.plan_blocks(m, n, k, cpw,
                                                          blocks)
                    assert bk % math.lcm(cpw * 8, 128) == 0
                    assert -(-k // cpw) > bkw
                want = quant_matmul_ref(x.astype(jnp.float32),
                                        jnp.asarray(codes),
                                        jnp.asarray(scale * mask))
                for backend in BACKENDS:
                    got = _packed_gemm(x, packed, bits, None, backend,
                                       rhs_ops=rhs_ops, blocks=blocks)
                    np.testing.assert_allclose(np.asarray(got),
                                               np.asarray(want),
                                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ LM serving
def _f32_lm(arch="internlm2-1.8b", bits_init=8.0):
    cfg = get_arch(arch, smoke=True)
    if cfg.dtype != "float32":        # tight parity needs f32 weights
        cfg = dataclasses.replace(cfg, dtype="float32")
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    qparams = lm.init_qparams(params, bits_init=bits_init)
    return lm, params, qparams


def _decode(lm, params, qparams, steps=4, batch=2):
    caches = lm.init_cache(batch, 16, dtype=jnp.float32)
    tok = jnp.zeros((batch, 1), jnp.int32)
    outs = []
    step = jax.jit(lm.decode_step)
    for p in range(steps):
        logits, caches = step(params, qparams, caches, tok, jnp.int32(p))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        outs.append(logits)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize(
    "arch,backend",
    [("internlm2-1.8b", None), ("rwkv6-3b", None),
     ("internlm2-1.8b", "pallas-interpret"), ("rwkv6-3b", "pallas-interpret")],
    ids=["internlm2-1.8b", "rwkv6-3b", "internlm2-1.8b-pallas-interpret",
         "rwkv6-3b-pallas-interpret"])
@pytest.mark.parametrize("bits_init", [8.0, 4.0], ids=["b8", "b4"])
def test_packed_decode_matches_unpacked(arch, bits_init, backend):
    """Packed and unpacked compressed decodes share codes and scales
    bit-for-bit, so logits agree (≤1e-4) and greedy tokens are identical
    — attn/MLP projections on the transformer, the rwkv time/channel-mix
    family on the SSM arch — with the packed decode's kernels on the
    platform's backend and in the Pallas interpreter."""
    lm, params, qparams = _f32_lm(arch, bits_init=bits_init)
    plain = compress_lm(lm, params, qparams)
    packed = compress_lm(lm, params, qparams, packed=True)
    assert packed.packed_bits
    for name, sb in packed.packed_bits.items():
        assert sb == int(np.ceil(packed.bits[name + ".wq"]))
        assert packed.int_weights[name].dtype == jnp.int32
    rq = residual_qparams(packed, qparams)
    want = _decode(lm, servable_params(plain),
                   residual_qparams(plain, qparams))
    with dispatch.use_backend(backend):
        got = _decode(lm, servable_params(packed), rq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.argmax(np.asarray(got), -1),
                          np.argmax(np.asarray(want), -1))


def test_packed_pruned_stacking_parity():
    """Sliced + packed — the full GETA deployment artifact: the packed
    decode on physically pruned (stacked per-period) shapes matches the
    unpacked pruned decode, and the served bytes shrink twice over."""
    lm_a = LM(_f32_lm()[0].cfg)
    lm_b = LM(lm_a.cfg)
    params_a, _ = lm_a.init(jax.random.PRNGKey(0))
    params_b, _ = lm_b.init(jax.random.PRNGKey(0))
    p_plain, q_plain, meta_plain = prepare_serving(
        lm_a, params_a, compressed=True, prune_sparsity=0.5)
    p_packed, q_packed, meta_packed = prepare_serving(
        lm_b, params_b, packed=True, prune_sparsity=0.5)
    assert meta_packed["sparsity"] == meta_plain["sparsity"] > 0.2
    assert meta_packed["param_bytes"] <= meta_plain["param_bytes"]
    want = _decode(lm_a, p_plain, q_plain)
    got = _decode(lm_b, p_packed, q_packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.argmax(np.asarray(got), -1),
                          np.argmax(np.asarray(want), -1))


def test_servable_params_packed_keys():
    """Packed sites ride the dict as `<name>.packed{bits}` (static width
    in the key), never alongside a `.codes` or dense copy."""
    lm, params, qparams = _f32_lm()
    subnet = compress_lm(lm, params, qparams, packed=True)
    sp = servable_params(subnet)
    assert subnet.packed_bits
    for name, sb in subnet.packed_bits.items():
        assert f"{name}.packed{sb}" in sp
        assert name + ".codes" not in sp
        assert name not in sp
        assert name + ".scale" in sp
        w = sp[f"{name}.packed{sb}"]
        if w.ndim >= 3:   # stacked site: scale broadcast over the stack
            assert sp[name + ".scale"].shape[0] == w.shape[0]
    assert subnet.meta["packed_sites"] == subnet.packed_bits


def test_packed_bytes_ratio_at_4_bits():
    """Acceptance: a mean-4-bit subnet's packed containers occupy ≤0.55x
    the unpacked int8 container bytes (4-bit packs 8 codes per int32 word
    — exactly 0.5x plus partial-word padding), and the served param dict
    shrinks accordingly."""
    lm, params, qparams = _f32_lm(bits_init=4.0)
    subnet = compress_lm(lm, params, qparams, packed=True)
    m = subnet.meta
    assert m["mean_bits"] == pytest.approx(4.0, abs=1e-3)
    assert m["weight_bytes_compressed"] <= 0.55 * m["weight_bytes_unpacked"]
    plain = compress_lm(lm, params, qparams)
    assert (tree_bytes(servable_params(subnet))
            < tree_bytes(servable_params(plain)))


# ---------------------------------------------------------- satellite fixes
def test_quantize_int_boundary_clamp():
    """Regression: at the bit-constraint boundary `round(xt/d)` can land
    on 2^(b-1) (128 at 8 bits), which wrapped negative in the int8
    container before the clamp. With the container width pinned at 8 bits
    (the layerwise constraint), codes must clamp to ±127."""
    qp = Q.QuantParams(d=jnp.float32(1.0 / 127.6), q_m=jnp.float32(1.0),
                       t=jnp.float32(1.0))
    x = jnp.asarray([1.0, -1.0, 0.5, 0.0])
    codes, _ = Q.quantize_int(x, qp, bits=8.0)
    as_i8 = np.asarray(codes.astype(jnp.int8))
    np.testing.assert_array_equal(as_i8, [127, -127, 64, 0])
    # the derived-width default clamps too: codes always fit the ceil
    # container quantize_int itself would pick
    codes_d, _ = Q.quantize_int(x, qp)
    b = int(np.ceil(float(Q.bit_width(qp.d, qp.q_m, qp.t))))
    assert np.max(np.abs(np.asarray(codes_d))) <= 2 ** (b - 1) - 1


def test_compression_report_explicit_zero_sparsity():
    """`--pruned --sparsity 0` ran the pruning path and must say so: the
    report keys on `is not None`, not truthiness."""
    rep = compression_report("arch", {"sparsity": 0.0})
    assert "pruned to sparsity 0.00" in rep
    # a compress-only meta carries no sparsity claim at all
    lm, params, qparams = _f32_lm()
    subnet = compress_lm(lm, params, qparams)
    assert "sparsity" not in subnet.meta
    assert "pruned" not in compression_report("arch", subnet.meta)


def test_pruned_zero_sparsity_report_via_prepare_serving():
    """End to end: an all-keep pruning run still reports its (0.00)
    sparsity line next to realized bytes."""
    lm, params, _ = _f32_lm()
    _, _, meta = prepare_serving(LM(lm.cfg), dict(params), compressed=True,
                                 prune_sparsity=0.0)
    assert meta["sparsity"] == 0.0
    assert "pruned to sparsity 0.00" in compression_report("arch", meta)


def test_mean_storage_bits_reported():
    """Satellite: the meta pairs float `mean_bits` with the integer-ceil
    `mean_storage_bits` the containers are sized from, so the report's
    bits and bytes figures agree."""
    lm, params, qparams = _f32_lm()
    for packed in (False, True):
        subnet = compress_lm(lm, params, qparams, packed=packed)
        m = subnet.meta
        assert m["mean_storage_bits"] == pytest.approx(float(np.mean(
            [np.ceil(b) for b in subnet.bits.values()])))
        assert m["mean_storage_bits"] >= m["mean_bits"] - 1e-6
        assert m["mean_storage_bits"] == float(int(m["mean_storage_bits"])) \
            or len({int(np.ceil(b)) for b in subnet.bits.values()}) > 1
    assert "storage" in compression_report("arch", subnet.meta)
