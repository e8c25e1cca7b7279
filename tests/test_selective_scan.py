"""Selective-scan kernel tier: Mamba-1's prompt scan as one Pallas launch
per layer (`kernels.selective_scan`) against the chunked XLA composition
(`ref.selective_scan_ref`), both float32.

- parity of y and the last state over prompt lengths that fill no whole
  token block or chunk, several token and channel blocks, channel counts
  that fill no lane tile (a pruned mixer's), batch > 1 and a nonzero
  starting state. The kernel walks the tokens one by one while the
  composition runs an associative scan inside each chunk, so they differ
  by f32 rounding alone: RTOL below;
- the op's gradient is the composition's (the kernel's custom VJP);
- a model prefill with the kernel on equals it with the kernel off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, use_backend

RTOL = 2e-5          # of the largest |y| or |h|: f32 rounding of ~S terms


def _inputs(B, S, Di, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(k[0], (B, S, Di), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, Di)) - 1.0)
    Bm = jax.random.normal(k[2], (B, S, N), jnp.bfloat16)
    Cm = jax.random.normal(k[3], (B, S, N), jnp.bfloat16)
    A = -jnp.arange(1, N + 1, dtype=jnp.float32)[None] \
        * jax.random.uniform(k[4], (Di, 1), minval=0.1, maxval=1.0)
    h0 = jax.random.normal(k[5], (B, Di, N), jnp.float32)
    return u, dt, Bm, Cm, A, h0


# (B, S, Di, N): a prompt shorter than a token group, one under a token
# block, two token blocks and a tail, channels under a lane tile and
# between lane tiles, and two 512-channel blocks
CASES = [(1, 5, 16, 4), (2, 37, 128, 8), (1, 300, 200, 16),
         (1, 130, 1024, 16)]


@pytest.mark.parametrize("B,S,Di,N", CASES)
def test_kernel_matches_the_chunked_scan(B, S, Di, N):
    args = _inputs(B, S, Di, N)
    y, h = ops.selective_scan_op(*args, chunk=16, backend="pallas-interpret")
    y_ref, h_ref = ref.selective_scan_ref(*args, chunk=16)
    assert y.shape == (B, S, Di) and h.shape == (B, Di, N)
    for got, want in ((y, y_ref), (h, h_ref)):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=RTOL * scale)


def test_kernel_gradient_is_the_compositions():
    args = _inputs(1, 21, 128, 8, seed=1)
    # a loss linear in y and h: the same cotangents reach both backwards
    wy, wh = (jax.random.normal(jax.random.PRNGKey(2), a.shape)
              for a in ref.selective_scan_ref(*args, chunk=8))

    def loss(backend):
        def f(u, dt, Bm, Cm, A, h0):
            y, h = ops.selective_scan_op(u, dt, Bm, Cm, A, h0, chunk=8,
                                         backend=backend)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return f

    got = jax.grad(loss("pallas-interpret"), argnums=range(6))(*args)
    want = jax.grad(loss("xla-ref"), argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_prefill_with_the_kernel_matches_without():
    """Jamba2-3B's smoke prefill (prompt 37, not a multiple of its
    16-token chunk): logits and every Mamba state leaf agree."""
    from repro.configs import get_arch
    from repro.models.transformer import LM
    lm = LM(get_arch("jamba2-3b", smoke=True))
    params, _ = lm.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 37), 0, 512)

    def run(backend):
        with use_backend(backend):
            return lm.prefill(params, None, lm.init_cache(1, 64), tokens)

    (lk, ck), (lr, cr) = run("pallas-interpret"), run("xla-ref")
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                               rtol=0, atol=1e-4)
    for key in cr:
        np.testing.assert_allclose(np.asarray(ck[key], np.float32),
                                   np.asarray(cr[key], np.float32),
                                   rtol=0, atol=1e-4, err_msg=key)
