"""Flash-decode attention kernel tier (DESIGN.md §4.9).

Four contracts:
- kernel-vs-oracle parity ≤ 1e-4 (pallas-interpret vs xla-ref) over
  ragged cache lengths, per-slot pos vectors, GQA ratios and windowed
  ring states — the same two-backend pin as the GEMM matrix tier;
- split-K chunk-count invariance: the online-softmax cross-chunk
  combine makes any chunking of the cache length produce the same
  attention (1 chunk vs 4 chunks agree to f32 roundoff);
- windowed-cache masking: a *fresh* ring (pos < ring_len) must mask its
  zero-initialized unwritten rows — the pre-kernel decode branch skipped
  the valid mask entirely for window > 0, so those rows received
  softmax weight (the regression this tier locks out);
- engine token-identity with the kernel on vs off across dense /
  pruned / packed serving (the `--decode-attn-parity` smoke contract).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import decode_attn as da
from repro.kernels import ops, ref, use_backend
from repro.models import layers as Lyr

ATOL = 1e-4

# (B, S, KVh, g, dh, chunk): ragged lengths, GQA ratios 1/2/3/8,
# sub-lane and multi-chunk cache lengths, non-128 head dims, and
# Jamba2-3B's multi-query attention (one KV head for 20 query heads,
# which the kernel pads to 24 rows)
CASES = [
    (1, 7, 1, 1, 4, None),
    (2, 33, 2, 3, 8, 16),
    (3, 64, 4, 2, 16, 16),
    (2, 130, 1, 8, 5, 32),
    (4, 24, 2, 1, 128, None),
    (3, 50, 1, 20, 128, 16),
]


def _case(seed, B, S, KVh, g, dh):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k1, (B, KVh, g, dh))
    k = jax.random.normal(k2, (B, S, KVh, dh))
    v = jax.random.normal(k3, (B, S, KVh, dh))
    pos = jax.random.randint(k4, (B,), 0, S)
    return q, k, v, pos


@pytest.mark.parametrize("case", CASES,
                         ids=[f"B{b}S{s}KV{h}g{g}dh{d}" for b, s, h, g, d, _
                              in CASES])
def test_kernel_vs_oracle_parity(case):
    B, S, KVh, g, dh, chunk = case
    q, k, v, pos = _case(sum(case[:5]), B, S, KVh, g, dh)
    want = ref.decode_attn_ref(q, k, v, pos)
    got = da.decode_attn_pallas(q, k, v, pos, chunk=chunk, interpret=True)
    assert got.shape == want.shape == (B, KVh, g, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=ATOL)


def test_per_slot_pos_extremes():
    """Every slot at its own progress, including rows 0 (single valid
    slot) and S-1 (whole arena valid)."""
    B, S, KVh, g, dh = 4, 40, 2, 2, 8
    q, k, v, _ = _case(7, B, S, KVh, g, dh)
    pos = jnp.asarray([0, S - 1, 17, 3], jnp.int32)
    want = ref.decode_attn_ref(q, k, v, pos)
    got = da.decode_attn_pallas(q, k, v, pos, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=ATOL)
    # pos = 0 attends over exactly one arena row: the output is v[:, 0]
    # regardless of scores — pins the valid-length mask edge
    expect = np.broadcast_to(np.asarray(v)[0, 0][:, None, :], (KVh, g, dh))
    np.testing.assert_allclose(np.asarray(got[0]), expect,
                               rtol=1e-4, atol=ATOL)


def test_windowed_ring_states():
    """Fresh ring (pos < ring_len: only the first pos+1 rows written) and
    wrapped ring (pos >= ring_len: every row written) both follow the
    min(pos+1, S) rule — fresh masks the unwritten tail, wrapped attends
    over the full ring."""
    B, S, KVh, g, dh = 2, 16, 2, 2, 8
    q, k, v, _ = _case(11, B, S, KVh, g, dh)
    # fresh: pos=5 -> rows [0, 5] valid; the oracle over the sliced cache
    # is the ground truth (no masking needed there at pos = S'-1)
    pos = jnp.asarray([5, 5], jnp.int32)
    got = da.decode_attn_pallas(q, k, v, pos, window=S, chunk=8,
                                interpret=True)
    sliced = ref.decode_attn_ref(q, k[:, :6], v[:, :6],
                                 jnp.asarray([5, 5], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(sliced),
                               rtol=1e-4, atol=ATOL)
    # wrapped: pos >= S -> all rows valid, mask saturates at S
    pos = jnp.asarray([S + 9, 5 * S], jnp.int32)
    got = da.decode_attn_pallas(q, k, v, pos, window=S, chunk=8,
                                interpret=True)
    want = ref.decode_attn_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=ATOL)
    # and the full-arena mask at pos = S-1 equals the wrapped ring: both
    # attend over every row
    same = da.decode_attn_pallas(q, k, v, jnp.full((B,), S - 1), chunk=8,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                               rtol=1e-6, atol=1e-6)


def test_split_k_chunk_invariance():
    """1 chunk vs 4 chunks: the cross-chunk rescale combine reproduces
    the single-pass softmax to f32 roundoff."""
    B, S, KVh, g, dh = 2, 64, 2, 4, 16
    q, k, v, pos = _case(13, B, S, KVh, g, dh)
    one = da.decode_attn_pallas(q, k, v, pos, chunk=64, interpret=True)
    four = da.decode_attn_pallas(q, k, v, pos, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(four),
                               rtol=1e-6, atol=1e-6)
    want = ref.decode_attn_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(four), np.asarray(want),
                               rtol=1e-4, atol=ATOL)


def test_op_backend_dispatch():
    """`ops.decode_attn_op` routes through the dispatch registry: xla-ref
    is the oracle bit-for-bit, pallas-interpret agrees to the parity
    tier's tolerance."""
    B, S, KVh, g, dh = 2, 20, 2, 2, 8
    q, k, v, pos = _case(17, B, S, KVh, g, dh)
    want = ref.decode_attn_ref(q, k, v, pos)
    with use_backend("xla-ref"):
        got = ops.decode_attn_op(q, k, v, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = ops.decode_attn_op(q, k, v, pos, backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=ATOL)


# ------------------------------------------------- the stacked arena read
# (KVh, S, chunk, ring): the cells' 8 and 4 KV heads at GQA 2; an arena
# that is not a whole number of chunks (the padded path); a ring past
# its wrap
ARENA_CASES = [
    (8, 64, 16, False),
    (4, 64, 16, False),
    (4, 40, 16, False),
    (8, 48, 16, True),
]


@pytest.mark.parametrize("case", ARENA_CASES,
                         ids=[f"KV{h}S{s}c{c}{'ring' if r else ''}"
                              for h, s, c, r in ARENA_CASES])
def test_stacked_arena_layer_matches_sliced_layer(case):
    """The kernel reading layer l of the stacked (nb, B, S, KVh*dh) arena
    in place is bitwise the kernel on that layer sliced out beforehand,
    for every l traced (as the layer scan passes it) and for a static l;
    both match the oracle. Slots sit at row 0, mid-chunk and the last
    row (a ring: past its wrap)."""
    KVh, S, chunk, ring = case
    nb, B, g, dh = 3, 3, 2, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S + KVh), 3)
    q = jax.random.normal(k1, (B, KVh, g, dh))
    ka = jax.random.normal(k2, (nb, B, S, KVh * dh))
    va = jax.random.normal(k3, (nb, B, S, KVh * dh))
    pos = (jnp.asarray([S + 3, 2 * S + chunk // 2, 3 * S - 1]) if ring
           else jnp.asarray([0, chunk + chunk // 2, S - 1])).astype(jnp.int32)
    window = S if ring else 0
    kw = dict(window=window, chunk=chunk, interpret=True)
    in_place = jax.jit(lambda l: da.decode_attn_pallas(q, ka, va, pos,
                                                       layer=l, **kw))
    sliced = jax.jit(lambda kh, vh: da.decode_attn_pallas(q, kh, vh, pos,
                                                          **kw))
    for l in range(nb):
        kh = ka[l].reshape(B, S, KVh, dh)
        vh = va[l].reshape(B, S, KVh, dh)
        want = np.asarray(sliced(kh, vh))
        np.testing.assert_array_equal(np.asarray(in_place(jnp.int32(l))),
                                      want)
        np.testing.assert_allclose(
            want, np.asarray(ref.decode_attn_ref(q, kh, vh, pos)),
            rtol=1e-4, atol=ATOL)
    # a static index (the unrolled shallow-model branch) reads alike
    np.testing.assert_array_equal(
        np.asarray(da.decode_attn_pallas(q, ka, va, pos, layer=nb - 1, **kw)),
        want)


def test_op_reads_a_stacked_layer():
    """`ops.decode_attn_op` with a layer index: xla-ref slices the layer
    and is the oracle bit-for-bit; pallas-interpret reads it in place."""
    nb, B, S, KVh, g, dh = 2, 2, 20, 2, 2, 8
    q, _, _, pos = _case(19, B, S, KVh, g, dh)
    ka = jax.random.normal(jax.random.PRNGKey(5), (nb, B, S, KVh * dh))
    va = jax.random.normal(jax.random.PRNGKey(6), (nb, B, S, KVh * dh))
    want = ref.decode_attn_ref(q, ka[1].reshape(B, S, KVh, dh),
                               va[1].reshape(B, S, KVh, dh), pos)
    got = ops.decode_attn_op(q, ka, va, pos, layer=1, backend="xla-ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = ops.decode_attn_op(q, ka, va, pos, layer=jnp.int32(1),
                             backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=ATOL)


# ------------------------------------------------- windowed decode masking
def _tiny_cfg(window: int) -> ModelConfig:
    return ModelConfig(name="tiny-windowed", family="dense", n_layers=1,
                       d_model=16, n_heads=4, n_kv_heads=2, d_head=4,
                       d_ff=32, vocab=64, window=window, dtype="float32")


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "einsum"])
def test_fresh_windowed_cache_masks_unwritten_rows(kernel):
    """Regression: decoding from a *fresh* windowed cache (pos < ring_len)
    must ignore the ring's zero-initialized unwritten rows.

    While pos < window the sliding window isn't binding and the ring
    hasn't wrapped, so a windowed layer must produce exactly the
    full-causal layer's output; before the fix the windowed branch
    applied no valid-length mask at all, giving the zero rows softmax
    weight (score 0 instead of -inf) and dragging the output toward the
    unnormalized mean."""
    W = 6
    cfgw = _tiny_cfg(window=W)
    cfg0 = dataclasses.replace(cfgw, window=0)
    params, _ = Lyr.init_attention(jax.random.PRNGKey(0), cfgw,
                                   "blocks.0.attn", 0, jnp.float32)
    B, row = 2, cfgw.n_kv_heads * cfgw.d_head
    ring = (jnp.zeros((B, W, row)), jnp.zeros((B, W, row)))
    full = (jnp.zeros((B, 12, row)), jnp.zeros((B, 12, row)))
    with Lyr.use_decode_attn(kernel):
        for t in range(4):   # strictly pre-wrap: t < W
            x = jax.random.normal(jax.random.PRNGKey(100 + t),
                                  (B, 1, cfgw.d_model))
            rope = Lyr.rope_tables(1, cfgw.d_head, cfgw.rope_theta, offset=t)
            outw, cw = Lyr.attn_apply(params, None, cfgw, x, rope=rope,
                                      window=W, prefix="blocks.0.attn",
                                      cache=ring + (jnp.int32(t),))
            out0, c0 = Lyr.attn_apply(params, None, cfg0, x, rope=rope,
                                      window=0, prefix="blocks.0.attn",
                                      cache=full + (jnp.int32(t),))
            ring, full = (cw[0], cw[1]), (c0[0], c0[1])
            np.testing.assert_allclose(
                np.asarray(outw), np.asarray(out0), rtol=1e-5, atol=1e-5,
                err_msg=f"fresh windowed decode diverged from full-causal "
                        f"at pos {t} (unwritten ring rows got weight?)")


# --------------------------------------------------- engine token identity
@pytest.mark.parametrize("mode", ["dense", "pruned_s50", "packed_b4"])
def test_engine_token_identity_kernel_on_vs_off(mode):
    """Engine decode with the flash-decode kernel is token-identical to
    the legacy einsum path on the same weights/prompts/seed — per serving
    mode (the kernel must compose with SlimPlan head counts and packed
    codes). Exactly the `serve --smoke --decode-attn-parity` contract."""
    from repro.launch.serve import decode_attn_parity_check
    kw = {
        "dense": {},
        "pruned_s50": dict(compressed=True, pruned=True, sparsity=0.5),
        "packed_b4": dict(packed=True, bits_init=4.0),
    }[mode]
    out = decode_attn_parity_check("internlm2-1.8b", True, [7, 4], 6,
                                   max_slots=2, verbose=False, **kw)
    assert sorted(out) == [0, 1]
    assert all(len(t) == 6 for t in out.values())
