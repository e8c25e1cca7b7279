"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Interpret mode (the CPU parity tier) runs any block shape; the TPU
lowering does not. Every kernel here is lowered and compiled against a
*described* v5e chip (`jax.experimental.topologies`), so an unaligned
BlockSpec or an unlowerable op fails in CI, not on the chip. Nothing
executes: shapes only.

Widths are internlm2-1.8b's (d_model 2048, d_ff 8192, 8 KV heads of
128, GQA 2): decode GEMMs at M=8 over (K, N) = (2048, 8192), flash
decode over an 8-slot, 2048-row arena, paged at 16 rows per page.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and the
suite runs under several workers. All compile tests live in this one
file so they share that process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attn as da
from repro.kernels import gemm_core, introspect, ops

M, K, N = 8, 2048, 8192
B, KVH, G, DH, S, PAGE = 8, 8, 2, 128, 2048, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep the cache off.
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the compiled program"
    return compiled


# ------------------------------------------------------------------ GEMMs
def _col(sh):
    return _sds(sh, (N,), jnp.float32)


GEMM_CASES = {
    "plain": (jnp.bfloat16, None),
    "col_mask": (jnp.bfloat16, lambda c: (gemm_core.col_mask(c),)),
    "dequant": (jnp.int8, lambda c: (gemm_core.dequant(c),)),
    "unpack_dequant_b4": (4, lambda c: (gemm_core.unpack_dequant(4, c),)),
    "unpack_dequant_b3": (3, lambda c: (gemm_core.unpack_dequant(3, c),)),
    "unpack_dequant_b2": (2, lambda c: (gemm_core.unpack_dequant(2, c),)),
}


@pytest.mark.parametrize("epilogue", list(GEMM_CASES))
def test_gemm_epilogue_compiles(one_chip, epilogue):
    wdt, make_ops = GEMM_CASES[epilogue]
    if isinstance(wdt, int):      # packed word stream, 32 // bits per word
        w = _sds(one_chip, (-(-K // (32 // wdt)), N), jnp.int32)
    else:
        w = _sds(one_chip, (K, N), wdt)
    x = _sds(one_chip, (M, K), jnp.bfloat16)
    if make_ops is None:
        _compile(lambda x, w: gemm_core.gemm(x, w, (), backend="pallas-tpu"),
                 x, w)
    else:
        _compile(lambda x, w, c: gemm_core.gemm(
            x, w, make_ops(c), backend="pallas-tpu"), x, w, _col(one_chip))


def test_bits3_stream_launches_the_640_row_tile():
    """bits=3 packs 10 codes per word: bk = lcm(10 * 8, 128) = 640."""
    assert gemm_core.plan_blocks(M, N, K, k_pack=10) == (8, 128, 640, 64)


def test_fused_fake_quant_mask_forward_and_grad_compile(one_chip):
    x = _sds(one_chip, (M, K), jnp.bfloat16)
    w = _sds(one_chip, (K, N), jnp.bfloat16)
    mask = _col(one_chip)
    scal = [_sds(one_chip, (), jnp.float32)] * 3

    def fwd(x, w, mask, d, qm, t):
        return ops.fq_masked_matmul_op(x, w, mask, d, qm, t,
                                       backend="pallas-tpu")

    def loss(x, w, mask, d, qm, t):
        return jnp.sum(fwd(x, w, mask, d, qm, t).astype(jnp.float32))

    _compile(fwd, x, w, mask, *scal)
    _compile(jax.grad(loss, argnums=(0, 1, 3, 4, 5)), x, w, mask, *scal)


# ----------------------------------------------------------- flash decode
def test_contiguous_flash_decode_compiles(one_chip):
    q = _sds(one_chip, (B, KVH, G, DH), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, KVH, DH), jnp.bfloat16)
    pos = _sds(one_chip, (B,), jnp.int32)
    _compile(lambda q, k, v, p: da.decode_attn_pallas(q, k, v, p),
             q, kv, kv, pos)


@pytest.mark.parametrize("kv_bits", [None, 8, 4], ids=["bf16", "kv8", "kv4"])
def test_paged_flash_decode_compiles(one_chip, kv_bits):
    lp = S // PAGE
    n_pages = B * lp + 2
    dhs = DH // 2 if kv_bits == 4 else DH
    dt = jnp.bfloat16 if kv_bits is None else jnp.int8
    q = _sds(one_chip, (B, KVH, G, DH), jnp.bfloat16)
    pool = _sds(one_chip, (n_pages, PAGE, KVH, dhs), dt)
    pos = _sds(one_chip, (B,), jnp.int32)
    pt = _sds(one_chip, (B, lp), jnp.int32)
    args = [q, pool, pool, pos, pt]
    if kv_bits is not None:
        args += [_sds(one_chip, (n_pages, PAGE, KVH), jnp.float32)] * 2

    def fn(q, kp, vp, pos, pt, *scales):
        ks, vs = scales if scales else (None, None)
        return da.paged_decode_attn_pallas(
            q, kp, vp, pos, pt, page_size=PAGE, seq_len=S, kv_bits=kv_bits,
            k_scale=ks, v_scale=vs)

    _compile(fn, *args)


# --------------------------------------------- compiled vs the VMEM model
def _compile_recorded(fn, *args):
    """Compile, and return the launch record the trace noted with it."""
    with introspect.record_launches() as launches:
        compiled = _compile(fn, *args)
    (launch,) = launches
    return compiled, launch


def test_gemm_memory_matches_launch_model(one_chip):
    """The compiled program's HBM operands are exactly the launch record's
    (x + w + one column vector), and the modelled VMEM tile set fits the
    budget while staying far below them: the kernel streams, it does not
    stage whole operands."""
    x = _sds(one_chip, (M, K), jnp.bfloat16)
    w = _sds(one_chip, (K, N), jnp.int8)

    def fn(x, w, c):
        return gemm_core.gemm(x, w, (gemm_core.dequant(c),),
                              backend="pallas-tpu")

    compiled, launch = _compile_recorded(fn, x, w, _col(one_chip))
    ma = compiled.memory_analysis()
    assert launch.blocks == gemm_core.plan_blocks(M, N, K)
    hbm = M * K * 2 + K * N * launch.w_itemsize + launch.n_col * N * 4
    assert ma.argument_size_in_bytes == hbm
    vmem = introspect.launch_vmem_bytes(launch)
    assert vmem <= introspect.VMEM_BUDGET_BYTES
    assert vmem < ma.argument_size_in_bytes + ma.output_size_in_bytes


def test_decode_memory_matches_launch_model(one_chip):
    q = _sds(one_chip, (B, KVH, G, DH), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, KVH, DH), jnp.bfloat16)
    pos = _sds(one_chip, (B,), jnp.int32)

    def fn(q, k, v, p):
        return ops.decode_attn_op(q, k, v, p, backend="pallas-tpu")

    compiled, launch = _compile_recorded(fn, q, kv, kv, pos)
    ma = compiled.memory_analysis()
    assert (launch.gp, launch.dhs, launch.chunk) == (8, DH, 128)
    hbm = (B * KVH * G * DH * 2 + 2 * B * S * KVH * launch.dhs
           * launch.kv_itemsize + B * 4)
    # the (B,) positions occupy one 512-byte tile in HBM
    assert ma.argument_size_in_bytes == hbm - B * 4 + 512
    vmem = introspect.launch_vmem_bytes(launch)
    assert vmem <= introspect.VMEM_BUDGET_BYTES
    assert vmem < ma.argument_size_in_bytes
