"""Plain reference of Jamba (arXiv:2403.19887), written from the equations
of HF's `modeling_jamba.py`, for the comparison that decides a serving
cell's `correct`.

Layer i attends iff i % attn_layer_period == attn_layer_offset; every
other layer is a Mamba-1 mixer. Each layer is pre-norm with residuals,
and each is followed by a dense SwiGLU MLP (`num_experts` 1):

    h  = x + mixer(n1(x))
    x' = h + W_down (silu(W_gate n2(h)) * W_up n2(h))
    logits = n_f(x_L) E^T                      (tied head)

attention: causal softmax(q k^T / sqrt(dh)) v with one KV head shared by
every query head (MQA), no positional encoding;
Mamba:     u = silu(conv_b + causal depthwise conv(W_x n)), z = W_z n
           dt, B, C = n_dt(.), n_B(.), n_C(.) of W_xp u
           dt = softplus(W_dt dt + dt_bias), A = -exp(A_log)
           h_t = exp(dt_t A) h_{t-1} + dt_t u_t B_t,  y_t = <h_t, C_t>
           out = W_out ((y + D u) * silu(z))

n(x) = x / sqrt(mean(x^2) + eps) * g (RMSNorm). The recurrence runs one
token at a time (a `lax.scan` over positions, no chunking); attention
runs in blocks of queries so that teacher-forcing thousands of rows fits.

Departures from `modeling_jamba.py`: none in the equations. The weights
are the served ones as the benchmark made them (`bench/arch/jamba.py`),
upcast to float32; HF would round activations to the model's dtype
between operations, this reference does not. It imports nothing of the
program. Everything is float32 and matmuls run at `Precision.HIGHEST`.
`mode="fp8"` is the control: every matmul operand (projections, scores,
probabilities, values, the head) is rounded to float8 e4m3 with an
absmax scale per row or column; the recurrence stays float32.

It runs layer by layer (one compiled program per kind of layer, handed
that layer's weights) on a batch of sequences padded to a power of two
of at least `MIN_ROWS` rows and at most the arena's, so that it fits
beside the weights once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512       # queries per attention block
MIN_ROWS = 512


def _round_fp8(x, axis):
    """x rounded to float8 e4m3 under an absmax scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, mode):
    """a (..., K) @ b (K, N) in float32, or with fp8-rounded operands."""
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _round_fp8(a, -1), _round_fp8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mlp(h, lp, eps, mode):
    n2 = _rmsnorm(h, lp["norm2"], eps)
    gate = _mm(n2, lp["mlp.w_gate"], mode)
    up = _mm(n2, lp["mlp.w_up"], mode)
    return h + _mm(jax.nn.silu(gate) * up, lp["mlp.w_down"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _attn_layer(x, lp, *, dims, mode):
    H, dh, eps = dims
    B, T, _ = x.shape
    n1 = _rmsnorm(x, lp["norm1"], eps)
    q = _mm(n1, lp["attn.wq"], mode).reshape(B, T, H, dh)
    k = _mm(n1, lp["attn.wk"], mode)                  # (B, T, dh): one head
    v = _mm(n1, lp["attn.wv"], mode)
    if mode == "fp8":
        q, k, v = _round_fp8(q, -1), _round_fp8(k, -1), _round_fp8(v, 1)
    nq = T // Q_BLOCK if T % Q_BLOCK == 0 else 1
    qb = T // nq

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkd->bhqk", qi, k, precision=HI) / np.sqrt(dh)
        rows = i * qb + jnp.arange(qb)
        causal = jnp.arange(T)[None, :] <= rows[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if mode == "fp8":
            pr = _round_fp8(pr, -1)
        return jnp.einsum("bhqk,bkd->bqhd", pr, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(nq))            # (nq, B, qb, H, dh)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dh)
    return _mlp(x + _mm(o, lp["attn.wo"], mode), lp, eps, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _mamba_layer(x, lp, *, dims, mode):
    dtr, N, eps = dims
    B, T, _ = x.shape
    n1 = _rmsnorm(x, lp["norm1"], eps)
    xi = _mm(n1, lp["mamba.in_proj_x"], mode)         # (B, T, Di)
    z = _mm(n1, lp["mamba.in_proj_z"], mode)
    w = lp["mamba.conv_w"].astype(jnp.float32)        # (K, Di)
    K = w.shape[0]
    xp = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(xp[:, i:i + T] * w[i] for i in range(K))
    u = jax.nn.silu(u + lp["mamba.conv_b"].astype(jnp.float32))
    proj = _mm(u, lp["mamba.x_proj"], mode)
    dt = _rmsnorm(proj[..., :dtr], lp["mamba.dt_norm"], eps)
    Bm = _rmsnorm(proj[..., dtr:dtr + N], lp["mamba.b_norm"], eps)
    Cm = _rmsnorm(proj[..., dtr + N:], lp["mamba.c_norm"], eps)
    dt = jax.nn.softplus(_mm(dt, lp["mamba.dt_proj"], mode)
                         + lp["mamba.dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["mamba.A_log"].astype(jnp.float32))     # (Di, N)

    def step(h, inp):
        dt_t, u_t, b_t, c_t = inp                     # (B, Di) / (B, N)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    Di = A.shape[0]
    _, y = jax.lax.scan(step, jnp.zeros((B, Di, N), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (dt, u, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1) + lp["mamba.D"].astype(jnp.float32) * u
    out = _mm(y * jax.nn.silu(z), lp["mamba.out_proj"], mode)
    return _mlp(x + out, lp, eps, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_logits(x, pos, final_norm, embed, *, eps, mode):
    """Tied-head logits at the gathered positions pos (B, P) of x."""
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)
    xs = _rmsnorm(xs, final_norm, eps)
    e = embed.astype(jnp.float32)
    if mode == "fp8":
        xs, e = _round_fp8(xs, -1), _round_fp8(e, -1)
    return jnp.einsum("bpd,vd->bpv", xs, e, precision=HI)


@jax.jit
def _gap_to(ref_logits, tok):
    """How far the logit of `tok` lies below the reference's best."""
    best = jnp.max(ref_logits, -1)
    at = jnp.take_along_axis(ref_logits, tok[..., None], -1)[..., 0]
    return best - at


def _layer_params(params: dict, cfg: dict, i: int) -> tuple[str, dict]:
    """Kind of layer i and its weights, named without the block prefix."""
    P = cfg["attn_layer_period"]
    j, blk = i % P, i // P
    pre = f"blocks.{j}."
    kind = "attn" if j == cfg["attn_layer_offset"] else "mamba"
    return kind, {k[len(pre):]: v[blk] for k, v in params.items()
                  if k.startswith(pre)}


def hidden(params: dict, cfg: dict, tokens: np.ndarray, mode: str = "f32"):
    """Last layer's output (B, T, D) for token rows `tokens` (B, T)."""
    eps = float(cfg["rms_norm_eps"])
    H = cfg["num_attention_heads"]
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        kind, lp = _layer_params(params, cfg, i)
        if kind == "attn":
            x = _attn_layer(x, lp, dims=(H, cfg["hidden_size"] // H, eps),
                            mode=mode)
        else:
            x = _mamba_layer(x, lp, dims=(cfg["mamba_dt_rank"],
                                          cfg["mamba_d_state"], eps),
                             mode=mode)
    return x


def logits(params: dict, cfg: dict, tokens: np.ndarray, mode: str = "f32"):
    """Reference logits (B, T, Vp) at every position of `tokens` (B, T)."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return _head_logits(hidden(params, cfg, tokens, mode), pos,
                        params["final_norm"], params["embed"],
                        eps=float(cfg["rms_norm_eps"]), mode=mode)


def _padded_rows(n: int, rows: int) -> int:
    """Rows a batch whose longest sequence has n tokens is padded to."""
    t = MIN_ROWS
    while t < n:
        t *= 2
    return min(t, rows)


def served_gaps(params: dict, cfg: dict, requests: list[dict], rows: int,
                batch: int = 4, control: bool = False) -> list[np.ndarray]:
    """For each served request {"prompt": ids, "tokens": served ids}, the
    gap of each served token: how far the reference's logit of that token
    lies below the reference's best at its position. Teacher-forced over
    the prompt and the served tokens, padded (`_padded_rows`).

    With `control`, the gap is instead that of the token the fp8 control
    puts first at each of those positions."""
    eps = float(cfg["rms_norm_eps"])
    head = (params["final_norm"], params["embed"])
    out = []
    for b0 in range(0, len(requests), batch):
        chunk = requests[b0:b0 + batch]
        P = max(len(r["tokens"]) for r in chunk)
        n = max(r["prompt"].size + len(r["tokens"]) - 1 for r in chunk)
        if n > rows:
            raise ValueError(f"request of {n} rows > {rows}")
        toks = np.zeros((len(chunk), _padded_rows(n, rows)), np.int32)
        pos = np.zeros((len(chunk), P), np.int32)
        tgt = np.zeros((len(chunk), P), np.int32)
        for i, r in enumerate(chunk):
            seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
            toks[i, :seq.size] = seq
            m = len(r["tokens"])
            pos[i, :m] = np.arange(m) + r["prompt"].size - 1
            pos[i, m:] = pos[i, m - 1]
            tgt[i, :m] = r["tokens"]
        ref = _head_logits(hidden(params, cfg, toks), jnp.asarray(pos),
                           *head, eps=eps, mode="f32")
        if control:
            low = _head_logits(hidden(params, cfg, toks, mode="fp8"),
                               jnp.asarray(pos), *head, eps=eps, mode="fp8")
            pick = jnp.argmax(low, -1).astype(jnp.int32)
            del low
        else:
            pick = jnp.asarray(tgt)
        gap = np.asarray(_gap_to(ref, pick))
        del ref
        for i, r in enumerate(chunk):
            out.append(gap[i, :len(r["tokens"])])
    return out
