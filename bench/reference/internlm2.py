"""Plain reference of InternLM2 (arXiv:2403.17297), written from its
equations, for the comparison that decides a serving cell's `correct`.

    x_0 = E[tokens]
    h   = x + W_o attn(rope(W_q n1(x)), rope(W_k n1(x)), W_v n1(x))
    x'  = h + W_down (silu(W_gate n2(h)) * W_up n2(h))
    logits = head(n_f(x_L))

n(x) = x / sqrt(mean(x^2) + eps) * g (RMSNorm); rope rotates the two
halves of each head by position * theta ** (-2i / d_head) (rotate-half
form); attention is causal softmax(q k^T / sqrt(d_head)) v with each KV
head shared by H / KVh query heads (grouped-query attention).

It reads the served weights as the benchmark made them (`benchlib.weights`
describes the layout), dequantizing packed codes with plain jnp, and
imports nothing of the program. Everything is float32, and matmuls run at
`Precision.HIGHEST`. `mode="fp8"` is the control: every matmul operand is
rounded to float8 e4m3 with an absmax scale per row or column (the step
below bf16 that a later change might take).

It runs layer by layer (one compiled layer, indexed by a traced layer
number) on a batch of sequences padded to one length, so that it fits
beside the weights once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _round_fp8(x, axis):
    """x rounded to float8 e4m3 under an absmax scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, mode):
    """a (..., K) @ b (K, N) in float32, or with fp8-rounded operands."""
    if mode == "fp8":
        a, b = _round_fp8(a, -1), _round_fp8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def unpack(words, bits: int, k: int):
    """Signed `bits`-wide codes from int32 words packed along axis 0,
    least significant field first; the first k rows."""
    cpw = 32 // bits
    shifts = jnp.arange(cpw, dtype=jnp.int32) * bits
    fields = (words[:, None, :] >> shifts[None, :, None]) & ((1 << bits) - 1)
    sign = 1 << (bits - 1)
    codes = (fields ^ sign) - sign
    return codes.reshape(words.shape[0] * cpw, words.shape[1])[:k]


def weight(p: dict, name: str, k: int, layer=None):
    """The served weight `name` as float32 (K, N): bf16 as stored, or
    packed codes times their step."""
    def at(a):
        return a if layer is None else a[layer]
    if name in p:
        return at(p[name]).astype(jnp.float32)
    for key in p:
        if key.startswith(name + ".packed"):
            bits = int(key[len(name) + len(".packed"):])
            codes = unpack(at(p[key]), bits, k).astype(jnp.float32)
            return codes * at(p[name + ".scale"]).astype(jnp.float32)
    raise KeyError(f"no served weight {name}")


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    """x (B, T, heads, dh); cos/sin (T, dh/2)."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_tables(T: int, dh: int, theta: float):
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer(x, blocks, layer, cos, sin, *, dims, mode):
    D, H, KVh, dh, F, eps = dims
    B, T, _ = x.shape
    pre = "blocks.0."
    n1 = _rmsnorm(x, blocks[pre + "norm1"][layer], eps)
    q = _mm(n1, weight(blocks, pre + "attn.wq", D, layer), mode)
    k = _mm(n1, weight(blocks, pre + "attn.wk", D, layer), mode)
    v = _mm(n1, weight(blocks, pre + "attn.wv", D, layer), mode)
    q = _rope(q.reshape(B, T, H, dh), cos, sin)
    k = _rope(k.reshape(B, T, KVh, dh), cos, sin)
    v = v.reshape(B, T, KVh, dh)
    g = H // KVh
    k = jnp.repeat(k, g, axis=2)                      # head h reads KV h // g
    v = jnp.repeat(v, g, axis=2)
    if mode == "fp8":
        q, k, v = _round_fp8(q, -1), _round_fp8(k, -1), _round_fp8(v, 1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(dh)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if mode == "fp8":
        pr = _round_fp8(pr, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI).reshape(B, T, H * dh)
    h = x + _mm(o, weight(blocks, pre + "attn.wo", H * dh, layer), mode)
    n2 = _rmsnorm(h, blocks[pre + "norm2"][layer], eps)
    gate = _mm(n2, weight(blocks, pre + "mlp.w_gate", D, layer), mode)
    up = _mm(n2, weight(blocks, pre + "mlp.w_up", D, layer), mode)
    return h + _mm(jax.nn.silu(gate) * up,
                   weight(blocks, pre + "mlp.w_down", F, layer), mode)


@functools.partial(jax.jit, static_argnames=("D", "eps", "mode"))
def _head_logits(x, pos, final_norm, head_p, *, D, eps, mode):
    """Logits at the gathered positions pos (B, P) of x (B, T, D)."""
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)
    xs = _rmsnorm(xs, final_norm, eps)
    return _mm(xs, weight(head_p, "head", D), mode)


@jax.jit
def _gap_to(ref_logits, tok):
    """How far the logit of `tok` lies below the reference's best."""
    best = jnp.max(ref_logits, -1)
    at = jnp.take_along_axis(ref_logits, tok[..., None], -1)[..., 0]
    return best - at


def dims_of(cfg: dict, params: dict) -> tuple:
    """(D, H, KVh, dh, F, eps) read off the served weights' shapes."""
    D, dh = cfg["hidden_size"], cfg["head_dim"]
    kv = _out_dim(params, "blocks.0.attn.wk")
    q = _out_dim(params, "blocks.0.attn.wq")
    F = _out_dim(params, "blocks.0.mlp.w_gate")
    return (D, q // dh, kv // dh, dh, F, float(cfg["rms_norm_eps"]))


def _out_dim(params, name):
    for key, a in params.items():
        if key == name or key.startswith(name + ".packed"):
            return int(a.shape[-1])
    raise KeyError(name)


def hidden(params: dict, cfg: dict, tokens: np.ndarray, mode: str = "f32"):
    """Last layer's output (B, T, D) for token rows `tokens` (B, T)."""
    dims = dims_of(cfg, params)
    blocks = {k: v for k, v in params.items() if k.startswith("blocks.")}
    cos, sin = rope_tables(tokens.shape[1], cfg["head_dim"],
                           float(cfg["rope_theta"]))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(x, blocks, jnp.int32(layer), cos, sin, dims=dims,
                   mode=mode)
    return x


def served_gaps(params: dict, cfg: dict, requests: list[dict], rows: int,
                batch: int = 4, control: bool = False) -> list[np.ndarray]:
    """For each served request {"prompt": ids, "tokens": served ids}, the
    gap of each served token: how far the reference's logit of that token
    lies below the reference's best at its position. Teacher-forced over
    the prompt and the served tokens, padded to `rows` (one shape to
    compile).

    With `control`, the gap is instead that of the token the fp8 control
    puts first at each of those positions."""
    D, eps = cfg["hidden_size"], float(cfg["rms_norm_eps"])
    head_p = {k: v for k, v in params.items() if k.startswith("head")}
    out = []
    for b0 in range(0, len(requests), batch):
        chunk = requests[b0:b0 + batch]
        P = max(len(r["tokens"]) for r in chunk)
        toks = np.zeros((len(chunk), rows), np.int32)
        pos = np.zeros((len(chunk), P), np.int32)
        tgt = np.zeros((len(chunk), P), np.int32)
        for i, r in enumerate(chunk):
            seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
            if seq.size > rows:
                raise ValueError(f"request of {seq.size} rows > {rows}")
            toks[i, :seq.size] = seq
            n = len(r["tokens"])
            pos[i, :n] = np.arange(n) + r["prompt"].size - 1
            pos[i, n:] = pos[i, n - 1]
            tgt[i, :n] = r["tokens"]
        ref = _head_logits(hidden(params, cfg, toks), jnp.asarray(pos),
                           params["final_norm"], head_p, D=D, eps=eps,
                           mode="f32")
        if control:
            low = _head_logits(hidden(params, cfg, toks, mode="fp8"),
                               jnp.asarray(pos), params["final_norm"],
                               head_p, D=D, eps=eps, mode="fp8")
            pick = jnp.argmax(low, -1).astype(jnp.int32)
            del low
        else:
            pick = jnp.asarray(tgt)
        gap = np.asarray(_gap_to(ref, pick))
        del ref
        for i, r in enumerate(chunk):
            out.append(gap[i, :len(r["tokens"])])
    return out
