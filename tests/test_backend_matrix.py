"""Backend-parity matrix: every GEMM epilogue the models can emit, swept
across the kernel backends on odd/ragged (non-MXU-aligned) shapes.

One parameterized test covers the full product

    {col_mask, dequant(int8), dequant(int16), fake_quant, fused joint,
     unpack_dequant b2/b3/b4/b8}
      x {pallas-interpret vs xla-ref}
      x ragged (M, K, N) sweeps,

asserting the Pallas kernel logic and the pure-jnp oracle agree to <=1e-4.
`test_gemm_core.py` checks each op against its *ref oracle*; this matrix
pins the two *backends* against each other through the public `gemm()`
entry point, so a padding/tiling regression in either backend trips the
same test cell that names it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gemm_core

# deliberately ragged: primes, 1-row/1-col edges, > one block in each dim,
# decode-shaped small-M rows (M = active slots; exercises the aligned
# small-M bm clamp in gemm_core._clamp_blocks, and the packed plan's
# decode branch up to the artifact's 28 slots) and a prefill-shaped one
RAGGED_SHAPES = [(1, 1, 1), (1, 7, 5), (3, 193, 17), (29, 31, 37),
                 (57, 384, 129), (130, 257, 131),
                 (4, 256, 128), (8, 96, 160), (28, 300, 260),
                 (300, 1030, 131)]

ATOL = 1e-4


def _w_and_ops(key, kind, k, n):
    """Build (rhs tensor, epilogue ops) for one matrix cell."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    mask = (jax.random.uniform(k2, (n,)) > 0.35).astype(jnp.float32)
    d, qm, t = jnp.float32(0.05), jnp.float32(1.3), jnp.float32(0.9)
    if kind == "col_mask":
        return jax.random.normal(k1, (k, n)), (gemm_core.col_mask(mask),)
    if kind in ("dequant_int8", "dequant_int16"):
        # scale ~ q_m / 2^(bits-1): effective weights stay O(1), like the
        # codes `construct_subnet` actually emits
        dt = jnp.int8 if kind == "dequant_int8" else jnp.int16
        hi = 127 if kind == "dequant_int8" else 32000
        codes = jax.random.randint(k1, (k, n), -hi, hi).astype(dt)
        scale = (jax.random.uniform(k2, (n,)) + 0.5) * (2.0 / hi)
        return codes, (gemm_core.dequant(scale),)
    if kind.startswith("unpack_dequant"):
        # sub-byte packed codes: int32 word stream along K (bits=3 covers
        # the 10-codes-per-word stream, whose K blocks align to 640 codes)
        from repro.core.quant import pack_codes
        bits = int(kind[-1])
        hi = 2 ** (bits - 1) - 1
        codes = jax.random.randint(k1, (k, n), -hi, hi + 1).astype(jnp.int8)
        scale = (jax.random.uniform(k2, (n,)) + 0.5) * (2.0 / hi)
        return (pack_codes(codes, bits, axis=0),
                (gemm_core.unpack_dequant(bits, scale),))
    if kind == "fake_quant":
        return (jax.random.normal(k1, (k, n)) * 1.5,
                (gemm_core.fake_quant_rhs(d, qm, t),))
    assert kind == "fused_joint"
    return (jax.random.normal(k1, (k, n)) * 1.5,
            gemm_core.fq_mask_ops(d, qm, t, mask))


EPILOGUES = ["col_mask", "dequant_int8", "dequant_int16", "fake_quant",
             "fused_joint", "unpack_dequant_b4", "unpack_dequant_b3",
             "unpack_dequant_b2", "unpack_dequant_b8"]


@pytest.mark.parametrize("mkn", RAGGED_SHAPES,
                         ids=[f"{m}x{k}x{n}" for m, k, n in RAGGED_SHAPES])
@pytest.mark.parametrize("kind", EPILOGUES)
def test_epilogue_backend_matrix(kind, mkn):
    m, k, n = mkn
    seed = sum(ord(c) for c in kind) * 1009 + m * 7 + k * 11 + n * 13
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, k))
    w, rhs_ops = _w_and_ops(k * 31 + n, kind, k, n)
    y_pallas = gemm_core.gemm(x, w, rhs_ops, backend="pallas-interpret")
    y_ref = gemm_core.gemm(x, w, rhs_ops, backend="xla-ref")
    assert y_pallas.shape == (m, n) == y_ref.shape
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_ref),
                               rtol=1e-4, atol=ATOL)
