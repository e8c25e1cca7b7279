"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Interpret mode (the CPU parity tier) runs any block shape; the TPU
lowering does not. Every kernel here is lowered and compiled against a
*described* v5e chip (`jax.experimental.topologies`), so an unaligned
BlockSpec or an unlowerable op fails in CI, not on the chip. Nothing
executes: shapes only.

Widths are internlm2-1.8b's (d_model 2048, d_ff 8192, 8 KV heads of
128, GQA 2): decode GEMMs at M=8 over (K, N) = (2048, 8192), flash
decode over an 8-slot, 2048-row arena, paged at 16 rows per page. A
3-layer model's decode window and slot insert are compiled whole, to
pin that the stacked KV arena is written and read in place.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and the
suite runs under several workers. All compile tests live in this one
file so they share that process.
"""
import math
import os
import re

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
    """bits=3 packs 10 codes per word, so every K block is a multiple of
    lcm(10 * 8, 128) = 640 codes (64 words): the decode plan's whole-K
    block (205 words padded to 256); an explicit 128-code tile over a
    640-code K, which launches the 640-code tile itself; and the same
    tile over K = 2048, where K splits into lane-tiled 128-word blocks."""
    plans = [gemm_core.plan_blocks(M, N, K, k_pack=10),
             gemm_core.plan_blocks(M, N, 640, k_pack=10,
                                   blocks=(8, 128, 128)),
             gemm_core.plan_blocks(M, N, K, k_pack=10,
                                   blocks=(8, 128, 128))]
    assert plans == [(8, 1024, 2560, 256), (8, 128, 640, 64),
                     (8, 128, 1280, 128)]
    assert all(bk % 640 == 0 and bk == 10 * bkw for _, _, bk, bkw in plans)


# The GETA artifact's packed b4 GEMMs (internlm2-1.8b at 50% structured
# sparsity: 8 / 4 KV heads of 128, d_ff 4096, vocab 92672), at its 28
# decode slots and at a ~1k-token prefill.
ARTIFACT_GEMMS = {"wq": (2048, 1024), "wk": (2048, 512), "wv": (2048, 512),
                  "wo": (1024, 2048), "gate": (2048, 4096),
                  "up": (2048, 4096), "down": (4096, 2048),
                  "head": (2048, 92672)}


@pytest.mark.parametrize("rows", [28, 1020], ids=["decode", "prefill"])
@pytest.mark.parametrize("site", list(ARTIFACT_GEMMS))
def test_artifact_packed_gemm_takes_the_wide_plan(one_chip, site, rows):
    """Each packed projection of the artifact compiles for the chip with
    the plan by M: at decode a grid step streams at least 256 KiB of
    words (an 8 KiB step is bound by the fixed cost of a grid step), and
    every plan's modelled VMEM fits the budget."""
    k, n = ARTIFACT_GEMMS[site]
    x = _sds(one_chip, (rows, k), jnp.bfloat16)
    w = _sds(one_chip, (k // 8, n), jnp.int32)

    def fn(x, w, c):
        return gemm_core.gemm(x, w, (gemm_core.unpack_dequant(4, c),),
                              backend="pallas-tpu")

    _, launch = _compile_recorded(fn, x, w, _sds(one_chip, (n,),
                                                 jnp.float32))
    bm, bn, bk, bkw = launch.blocks
    assert launch.blocks == gemm_core.plan_blocks(rows, n, k, 8,
                                                  x_itemsize=2)
    assert launch.x_itemsize == 2            # bf16 planes for bf16 x
    assert n % bn == 0 and (k // 8) % bkw == 0   # no padded weight copy
    if rows <= gemm_core.DECODE_ROWS:
        assert bkw * bn * 4 >= 256 * 1024
        assert bk == k                       # K whole: x loaded once
    assert introspect.launch_vmem_bytes(launch) \
        <= introspect.VMEM_BUDGET_BYTES


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


def test_mqa_flash_decode_reads_the_stacked_arena(one_chip):
    """Jamba2-3B's decode attention: one KV head for 20 query heads (24
    rows once padded), reading layer l of the (2, 192, 9216, 128) arena
    its two attention layers stack, at its served widths."""
    q = _sds(one_chip, (192, 1, 20, 128), jnp.bfloat16)
    kv = _sds(one_chip, (2, 192, 9216, 128), jnp.bfloat16)
    pos = _sds(one_chip, (192,), jnp.int32)
    layer = _sds(one_chip, (), jnp.int32)
    _compile(lambda q, k, v, p, l: da.decode_attn_pallas(q, k, v, p,
                                                         layer=l),
             q, kv, kv, pos, layer)


@pytest.mark.parametrize("S", [366, 1020])
def test_selective_scan_compiles(one_chip, S):
    """Jamba2-3B's Mamba prefill scan (d_inner 5120, d_state 16) as one
    Pallas launch, at prompt lengths that fill no whole token block."""
    from repro.kernels import selective_scan as ss
    seq = _sds(one_chip, (1, S, 5120), jnp.bfloat16)
    dt = _sds(one_chip, (1, S, 5120), jnp.float32)
    bc = _sds(one_chip, (1, S, 16), jnp.bfloat16)
    _compile(ss.selective_scan_pallas, seq, dt, bc, bc,
             _sds(one_chip, (5120, 16), jnp.float32),
             _sds(one_chip, (1, 5120, 16), jnp.float32))


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


# ----------------------------------------------- the decode window in place
NB, SLOTS, ROWS = 3, 4, 1024          # layers, arena slots, arena rows
_COPYING = {"copy", "copy-start", "dynamic-slice", "reshape", "transpose",
            "pad", "slice", "concatenate", "fusion"}


def _instructions(hlo: str):
    """(name, opcode, element count) of every array-valued instruction."""
    pat = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    for line in hlo.splitlines():
        m = pat.match(line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            yield m.group(1), m.group(3), math.prod(dims), line


def _inplace_lm():
    from repro.configs.base import ModelConfig
    from repro.models.transformer import LM
    return LM(ModelConfig(name="inplace", family="dense", n_layers=NB,
                          d_model=512, n_heads=16, n_kv_heads=KVH,
                          d_head=DH, d_ff=1536, vocab=1000,
                          dtype="bfloat16"))


def _arena_moves(hlo: str) -> list[str]:
    """Ops that produce a layer's worth of the arena, or all of it, by
    slicing, copying, restacking or relayout."""
    layer = SLOTS * ROWS * KVH * DH
    return [line.strip()[:160] for name, op, n, line in _instructions(hlo)
            if n in (layer, NB * layer) and op in _COPYING
            and "dynamic-update-slice" not in name
            and "dynamic_update_slice" not in name]


def test_decode_window_reads_the_arena_in_place(one_chip):
    """`LM.decode_step` in a 2-step window scan with the arena donated,
    compiled for the chip: the arena aliases the window's output, no op
    slices, restacks, copies or relayouts a layer's worth of it (or all
    of it), and flash decode takes the stacked arena itself as its
    operand."""
    from repro.kernels import use_backend
    lm = _inplace_lm()

    def window(params, caches, tok, pos):
        def body(carry, _):
            caches, tok, pos = carry
            logits, caches = lm.decode_step(params, None, caches, tok, pos)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (caches, nxt[:, None], pos + 1), nxt

        (caches, _, _), toks = jax.lax.scan(body, (caches, tok, pos), None,
                                            length=2)
        return toks, caches

    shapes = (jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0))[0]),
              jax.eval_shape(lambda: lm.init_cache(SLOTS, ROWS)),
              jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
              jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    args = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), shapes)
    with use_backend("pallas-tpu"):
        compiled = jax.jit(window, donate_argnums=(1,)).lower(
            *args).compile()
    hlo = compiled.as_text()

    arena_bytes = NB * SLOTS * ROWS * KVH * DH * 2
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * arena_bytes
    moved = _arena_moves(hlo)
    assert not moved, "\n".join(moved)
    stacked = f"bf16[{NB},{SLOTS},{ROWS},{KVH * DH}]"
    calls = [line for line in hlo.splitlines()
             if "%flash_decode" in line and "custom-call(" in line]
    assert calls and all(line.count(stacked) >= 2 for line in calls), calls


def test_slot_insert_writes_the_arena_in_place(one_chip):
    """The engine's slot insert donates the arena: a prefilled row lands
    in it in place, with no copy of the arena."""
    from repro.launch.engine import Engine
    lm = _inplace_lm()
    params, _ = lm.init(jax.random.PRNGKey(0))
    eng = Engine(lm, params, None, max_slots=SLOTS, max_seq=ROWS)
    args = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (eng.caches, eng._fresh_row(), jnp.int32(0)))
    compiled = eng._insert.lower(*args).compile()
    arena_bytes = NB * SLOTS * ROWS * KVH * DH * 2
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * arena_bytes
    assert not _arena_moves(compiled.as_text())
