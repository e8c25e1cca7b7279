"""Plain reference of the `window_gqa` test architecture: InternLM2's
equations (`bench/reference/internlm2.py`, whose helpers it reuses and
does not change) with a sliding-window causal mask: query i attends keys
j with i - window < j <= i. Float32 at `Precision.HIGHEST`; `mode="fp8"`
is the control, every matmul operand rounded to float8 e4m3."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
base = harness.load_module(_ROOT, "reference", "internlm2")
HI = base.HI


def mask(T: int, window: int) -> np.ndarray:
    qi, ki = np.arange(T)[:, None], np.arange(T)[None, :]
    return (ki <= qi) & (ki > qi - window)


@functools.partial(jax.jit, static_argnames=("dims", "window", "mode"))
def _layer(x, blocks, layer, cos, sin, *, dims, window, mode):
    D, H, KVh, dh, F, eps = dims
    B, T, _ = x.shape
    pre = "blocks.0."

    def mm(a, name, k):
        return base._mm(a, base.weight(blocks, pre + name, k, layer), mode)

    n1 = base._rmsnorm(x, blocks[pre + "norm1"][layer], eps)
    q = base._rope(mm(n1, "attn.wq", D).reshape(B, T, H, dh), cos, sin)
    k = base._rope(mm(n1, "attn.wk", D).reshape(B, T, KVh, dh), cos, sin)
    v = mm(n1, "attn.wv", D).reshape(B, T, KVh, dh)
    k = jnp.repeat(k, H // KVh, axis=2)
    v = jnp.repeat(v, H // KVh, axis=2)
    if mode == "fp8":
        q, k, v = (base._round_fp8(q, -1), base._round_fp8(k, -1),
                   base._round_fp8(v, 1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(dh)
    s = jnp.where(mask(T, window)[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if mode == "fp8":
        pr = base._round_fp8(pr, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI).reshape(B, T, H * dh)
    h = x + mm(o, "attn.wo", H * dh)
    n2 = base._rmsnorm(h, blocks[pre + "norm2"][layer], eps)
    return h + mm(jax.nn.silu(mm(n2, "mlp.w_gate", D)) * mm(n2, "mlp.w_up", D),
                  "mlp.w_down", F)


def hidden(params: dict, cfg: dict, tokens: np.ndarray, mode: str = "f32"):
    dims = base.dims_of(cfg, params)
    blocks = {k: v for k, v in params.items() if k.startswith("blocks.")}
    cos, sin = base.rope_tables(tokens.shape[1], cfg["head_dim"],
                                float(cfg["rope_theta"]))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(x, blocks, jnp.int32(layer), cos, sin, dims=dims,
                   window=int(cfg["sliding_window"]), mode=mode)
    return x


def served_gaps(params: dict, cfg: dict, requests: list[dict], rows: int,
                batch: int = 4, control: bool = False) -> list[np.ndarray]:
    """As `bench/reference/internlm2.py`'s, under the windowed mask."""
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
            toks[i, :seq.size] = seq
            n = len(r["tokens"])
            pos[i, :n] = np.arange(n) + r["prompt"].size - 1
            pos[i, n:] = pos[i, n - 1]
            tgt[i, :n] = r["tokens"]

        def logits(mode):
            return base._head_logits(hidden(params, cfg, toks, mode),
                                     jnp.asarray(pos), params["final_norm"],
                                     head_p, D=D, eps=eps, mode=mode)

        ref = logits("f32")
        pick = (jnp.argmax(logits("fp8"), -1).astype(jnp.int32) if control
                else jnp.asarray(tgt))
        gap = np.asarray(base._gap_to(ref, pick))
        out.extend(gap[i, :len(r["tokens"])] for i, r in enumerate(chunk))
    return out
