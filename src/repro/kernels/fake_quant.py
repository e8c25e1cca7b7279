"""Fused fake-quant Pallas TPU kernel (forward + backward).

The paper applies the (d, q_m, t)-parameterized quantizer (Eqs 1-2) to every
weight and activation tensor. In eager frameworks this is a chain of ~8
elementwise HLOs, each a full HBM round-trip; on TPU we fuse the whole chain
into one VMEM-tiled pass.

Forward:   y = d * round(clip_{q_m}^t(|x|) / d) * sgn(x)
Backward:  dx (STE, zero outside the clip) plus *tile-local partial sums*
           for the three scalar gradients (Eqs 4-6). Each grid step writes
           its partial (dd, dq_m, dt) into a (grid_m, grid_n, 3) output that
           the wrapper reduces — this keeps the kernel embarrassingly
           parallel with no cross-tile accumulation hazards.

Tiling: (block_m, 128·k) blocks — the VPU operates on (8, 128) vregs, so the
last dim stays a multiple of 128 and the second-to-last a multiple of 8.
Scalars (d, q_m, t) are passed as (1, 1) blocks mapped to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-12
DEFAULT_BLOCK = (256, 512)


def _fwd_kernel(x_ref, d_ref, qm_ref, t_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    d = jnp.maximum(d_ref[0, 0], _EPS)
    qm = jnp.maximum(qm_ref[0, 0], _EPS)
    t = t_ref[0, 0]

    ax = jnp.abs(x)
    sign = jnp.sign(x)
    a = jnp.minimum(ax, qm)
    xt = jnp.exp(t * jnp.log(jnp.maximum(a, _EPS))) * (ax > 0)
    y = d * jnp.round(xt / d) * sign
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, d_ref, qm_ref, t_ref, g_ref, dx_ref, partial_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    d = jnp.maximum(d_ref[0, 0], _EPS)
    qm = jnp.maximum(qm_ref[0, 0], _EPS)
    t = t_ref[0, 0]

    ax = jnp.abs(x)
    sign = jnp.sign(x)
    inside = ax <= qm
    safe_ax = jnp.maximum(ax, _EPS)

    # dx: straight-through inside the clip range.
    dx_ref[...] = jnp.where(inside, g, 0.0).astype(dx_ref.dtype)

    # Shared shaped magnitude clip^t(|x|).
    a = jnp.minimum(ax, qm)
    xt = jnp.exp(t * jnp.log(jnp.maximum(a, _EPS))) * (ax > 0)

    # Eq (4): round(v) - v with v = clip^t / d.
    v = xt / d
    dd = jnp.sum(g * sign * (jnp.round(v) - v))

    # Eq (5): clip^t * log(clip_base), base = |x| inside, q_m outside.
    base = jnp.where(inside, safe_ax, qm)
    dt = jnp.sum(g * sign * jnp.exp(t * jnp.log(base)) * jnp.log(base))

    # Eq (6): 0 inside, t * q_m^{t-1} outside.
    dqm = jnp.sum(
        g * jnp.where(inside, 0.0, sign * t * jnp.exp((t - 1.0) * jnp.log(qm)))
    )

    # One 128-lane row per grid step (TPU-tileable; lanes 0..2 carry the
    # three scalar partials, the rest are zero).
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    row = jnp.where(lane == 0, dd,
                    jnp.where(lane == 1, dqm,
                              jnp.where(lane == 2, dt, 0.0)))
    partial_ref[...] = row


def _pad_to_2d(x):
    """Kernels tile a 2D view; fold leading dims, pad to block multiples."""
    shape = x.shape
    if x.ndim == 1:
        x2 = x.reshape(1, -1)
    else:
        x2 = x.reshape(-1, shape[-1])
    return x2, shape


def _block_for(shape2d, block):
    bm = min(block[0], max(8, shape2d[0]))
    bn = min(block[1], max(128, shape2d[1]))
    return bm, bn


def _pad(x2, bm, bn):
    m, n = x2.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x2 = jnp.pad(x2, ((0, pm), (0, pn)))
    return x2


def fake_quant_fwd_pallas(x, d, q_m, t, *, block=DEFAULT_BLOCK, interpret=False):
    x2, orig_shape = _pad_to_2d(x)
    bm, bn = _block_for(x2.shape, block)
    xp = _pad(x2, bm, bn)
    m, n = xp.shape
    grid = (m // bm, n // bn)
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(1, 1)
    sspec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))

    y = pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            sspec, sspec, sspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        name="fake_quant",
    )(xp, scal(d), scal(q_m), scal(t))
    return y[: x2.shape[0], : x2.shape[1]].reshape(orig_shape)


def fake_quant_bwd_pallas(x, d, q_m, t, g, *, block=DEFAULT_BLOCK,
                          interpret=False):
    x2, orig_shape = _pad_to_2d(x)
    g2, _ = _pad_to_2d(g)
    bm, bn = _block_for(x2.shape, block)
    xp = _pad(x2, bm, bn)
    gp = _pad(g2, bm, bn)
    m, n = xp.shape
    grid = (m // bm, n // bn)
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(1, 1)
    sspec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))

    gn = grid[1]
    dx, partials = pl.pallas_call(
        _bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((grid[0] * grid[1], 128), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            sspec, sspec, sspec,
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 128), lambda i, j: (i * gn + j, 0)),
        ),
        interpret=interpret,
        name="fake_quant_grad",
    )(xp, scal(d), scal(q_m), scal(t), gp)

    dx = dx[: x2.shape[0], : x2.shape[1]].reshape(orig_shape)
    sums = jnp.sum(partials, axis=0)
    return dx, sums[0], sums[1], sums[2]
