"""Mamba-1 selective scan over a whole prompt, one kernel per layer.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) B_t ;   y_t = <h_t, C_t>

per inner channel d (A, h: (d_state,) each). The XLA composition
(`ref.selective_scan_ref`) runs a loop over 64-token chunks with an
associative scan inside each: some twenty device ops a chunk and layer,
each moving (chunk, Di, N) f32 tensors whose N = 16 minor dim fills an
eighth of a vreg's lanes. This kernel keeps the state in VMEM and walks
the tokens in order, so a prefill is one launch per Mamba layer and the
(S, Di, N) transition tensors never exist:

  grid = (batch b, token block t, channel block d)   — d innermost

The state is held transposed, (N, d_block) with the channels on lanes,
for every channel block of the sequence in a (n_d, N, d_block) scratch,
carried across token blocks. A token's B_t / C_t must multiply every
lane of an (N, 128) slab: they arrive pre-broadcast along the lanes as
(B, S, N, 128) blocks, indexed by (b, t) only, so the pipeline fetches
them once per token block and every channel block reuses them. Tokens
are taken eight at a time (one sublane tile of dt and u); each token
updates the d_block / 128 slabs of the state and reads y_t out with a
sublane reduction.

The prompt is padded at the end to whole token blocks with dt = u = 0
(exp(0) = 1, no input: the state is left as it stands), and the channels
to whole lane tiles with A = dt = u = 0 and a zero state. Returns y
(without the D * u skip) and the last state, both float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 8           # tokens a loop iteration takes: one sublane tile
T_BLOCK = 128       # tokens a grid step takes
D_BLOCK = 512       # channels a grid step takes (four lane tiles)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref,
            h_scr, *, t_block: int, d_block: int):
    t = pl.program_id(1)
    d = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h_scr[d] = h0_ref[0]

    slabs = [slice(j * LANES, (j + 1) * LANES)
             for j in range(d_block // LANES)]
    a = a_ref[...]                                          # (N, d_block)
    rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, d_block), 0)

    def group(g, h):
        base = pl.multiple_of(g * GROUP, GROUP)
        dt = dt_ref[0, pl.ds(base, GROUP), :]               # (8, d_block)
        dtu = dt * u_ref[0, pl.ds(base, GROUP), :].astype(jnp.float32)
        y = jnp.zeros((GROUP, d_block), jnp.float32)
        for k in range(GROUP):
            b = b_ref[0, base + k]                          # (N, 128)
            c = c_ref[0, base + k]
            h = tuple(jnp.exp(a[:, s] * dt[k:k + 1, s]) * hj
                      + dtu[k:k + 1, s] * b for s, hj in zip(slabs, h))
            row = jnp.concatenate([jnp.sum(hj * c, axis=0, keepdims=True)
                                   for hj in h], axis=1)    # (1, d_block)
            y = jnp.where(rows == k, row, y)
        y_ref[0, pl.ds(base, GROUP), :] = y
        return h

    h = h_scr[d]
    h = jax.lax.fori_loop(0, t_block // GROUP, group,
                          tuple(h[:, s] for s in slabs))
    h = jnp.concatenate(h, axis=1)
    h_scr[d] = h
    h_ref[0] = h


def selective_scan_pallas(u, dt, Bm, Cm, A, h0, *, interpret: bool = False):
    """u (B, S, Di), dt (B, S, Di) f32, Bm / Cm (B, S, N), A (Di, N) f32,
    h0 (B, Di, N) f32 -> (y (B, S, Di) f32 without the D skip,
    h_last (B, Di, N) f32)."""
    Bsz, S, Di = u.shape
    N = A.shape[1]
    f32 = jnp.float32
    t_block = min(T_BLOCK, _round_up(S, GROUP))
    s_pad = _round_up(S, t_block)
    di_pad = _round_up(Di, LANES)
    d_block = D_BLOCK if di_pad % D_BLOCK == 0 else LANES
    n_t, n_d = s_pad // t_block, di_pad // d_block

    def seq(x):
        return jnp.pad(x, ((0, 0), (0, s_pad - S), (0, di_pad - Di)))

    def lanes(m):
        m = jnp.pad(m.astype(f32), ((0, 0), (0, s_pad - S), (0, 0)))
        return jnp.broadcast_to(m[..., None], (Bsz, s_pad, N, LANES))

    a_t = jnp.pad(A.astype(f32).T, ((0, 0), (0, di_pad - Di)))
    h0_t = jnp.pad(jnp.swapaxes(h0.astype(f32), 1, 2),
                   ((0, 0), (0, 0), (0, di_pad - Di)))
    seq_spec = pl.BlockSpec((1, t_block, d_block), lambda b, t, d: (b, t, d))
    bc_spec = pl.BlockSpec((1, t_block, N, LANES),
                           lambda b, t, d: (b, t, 0, 0))
    state_spec = pl.BlockSpec((1, N, d_block), lambda b, t, d: (b, 0, d))
    y, h = pl.pallas_call(
        functools.partial(_kernel, t_block=t_block, d_block=d_block),
        grid=(Bsz, n_t, n_d),
        in_specs=[seq_spec, seq_spec, bc_spec, bc_spec,
                  pl.BlockSpec((N, d_block), lambda b, t, d: (0, d)),
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((Bsz, s_pad, di_pad), f32),
                   jax.ShapeDtypeStruct((Bsz, N, di_pad), f32)],
        scratch_shapes=[pltpu.VMEM((n_d, N, d_block), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(seq(dt.astype(f32)), seq(u), lanes(Bm), lanes(Cm), a_t, h0_t)
    return y[:, :S, :Di], jnp.swapaxes(h[..., :Di], 1, 2)
