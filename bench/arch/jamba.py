"""Jamba (arXiv:2403.19887; HF `modeling_jamba.py`) as the benchmark serves
it: a hybrid decoder whose layer i attends iff i % attn_layer_period ==
attn_layer_offset, and is a Mamba-1 mixer otherwise, each followed by a
dense SwiGLU MLP (`num_experts` 1), under a tied head.

The architecture module's contract is `bench/arch/internlm2.py`'s. Here:

- the arena slot holds the attention layers' K and V rows and, whatever
  the length, each Mamba layer's state: h (d_inner, d_state) float32 and
  the last d_conv - 1 conv inputs in the served dtype;
- a decode step reads every weight once (the tied embedding once, as the
  head), the attended K/V rows, and reads and writes each active
  request's Mamba state once.

The served weights follow the program's served-parameter convention,
stacked over the layer blocks (nb = layers / period) per position j in
the period:

  embed (Vp, D), final_norm (D,); blocks.j.norm1 / norm2 (nb, D)
  attention j:  attn.wq (nb, D, H*dh), wk / wv (nb, D, KVh*dh),
                attn.wo (nb, H*dh, D)
  Mamba j:      mamba.in_proj_x / in_proj_z (nb, D, Di),
                conv_w (nb, K, Di), conv_b (nb, Di),
                x_proj (nb, Di, dtr + 2N), dt_norm (nb, dtr),
                b_norm / c_norm (nb, N), dt_proj (nb, dtr, Di),
                dt_bias (nb, Di), A_log (nb, Di, N), D (nb, Di),
                out_proj (nb, Di, D)
  every j:      mlp.w_gate / w_up (nb, D, F), w_down (nb, F, D)

Random weights from the seed: projections with spread fan_in ** -0.5,
norms near 1, A_log = log(1..N) (S4D-real, as HF initialises it), D = 1,
and dt_bias = softplus^-1 of a dt between 0.001 and 0.1, drawn
log-uniform as Mamba initialises it.

Counts are model FLOPs only: 2 per projection weight per token (head
included), 4 x q_dim per attended key per attention layer, and 4 x Di x N
per token per Mamba layer (the state update's multiply-add and the
readout's).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchlib import flops
from benchlib.weights import jax_seed, projection, vocab_padded

EMBED_STD = 0.02
NORM_JITTER = 0.05
CONV_B_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1


def _itemsize(cfg: dict) -> int:
    return 2 if cfg["torch_dtype"] == "bfloat16" else 4


def served_widths(cfg: dict) -> dict:
    L, P = cfg["num_hidden_layers"], cfg["attn_layer_period"]
    D = cfg["hidden_size"]
    n_attn = sum(1 for i in range(L) if i % P == cfg["attn_layer_offset"])
    return {
        "L": L, "P": P, "off": cfg["attn_layer_offset"], "D": D,
        "H": cfg["num_attention_heads"], "KVh": cfg["num_key_value_heads"],
        "dh": D // cfg["num_attention_heads"], "F": cfg["intermediate_size"],
        "Vp": vocab_padded(cfg["vocab_size"]),
        "Di": cfg["mamba_expand"] * D, "N": cfg["mamba_d_state"],
        "K": cfg["mamba_d_conv"], "dtr": cfg["mamba_dt_rank"],
        "n_attn": n_attn, "n_mamba": L - n_attn, "item": _itemsize(cfg),
    }


# ------------------------------------------------------------- program
def program_config(cfg: dict):
    """The program's model config; refuses what its Jamba mixer does not
    compute (experts, projection biases, no conv bias, a window)."""
    from repro.configs.base import MambaConfig, ModelConfig
    if cfg["num_experts"] != 1 or not cfg["mamba_conv_bias"] \
            or cfg["mamba_proj_bias"] or cfg.get("sliding_window"):
        raise ValueError(f"{cfg['name']}: the program serves Jamba with one "
                         f"dense MLP, a conv bias, no projection biases and "
                         f"full attention")
    w = served_widths(cfg)
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=w["L"], d_model=w["D"],
        n_heads=w["H"], n_kv_heads=w["KVh"], d_head=w["dh"], d_ff=w["F"],
        vocab=cfg["vocab_size"], attn_every=w["P"], attn_offset=w["off"],
        rope=False, tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        mamba=MambaConfig(d_state=w["N"], d_conv=w["K"],
                          expand=cfg["mamba_expand"], dt_rank=w["dtr"]),
        remat=False)


def slim_plan(cfg: dict, lm):
    """No Jamba artifact is configured: the published widths are served."""
    if cfg.get("artifact"):
        raise ValueError(f"{cfg['name']}: no Jamba artifact is served")
    return None


# ------------------------------------------------------------- weights
def projection_shapes(w: dict) -> dict[str, tuple[int, int, int]]:
    """(blocks, K, N) of each stacked projection."""
    D, Di, nb = w["D"], w["Di"], w["L"] // w["P"]
    q, kv = w["H"] * w["dh"], w["KVh"] * w["dh"]
    out = {}
    for j in range(w["P"]):
        pre = f"blocks.{j}."
        if j == w["off"]:
            out.update({pre + "attn.wq": (nb, D, q),
                        pre + "attn.wk": (nb, D, kv),
                        pre + "attn.wv": (nb, D, kv),
                        pre + "attn.wo": (nb, q, D)})
        else:
            out.update({pre + "mamba.in_proj_x": (nb, D, Di),
                        pre + "mamba.in_proj_z": (nb, D, Di),
                        pre + "mamba.x_proj": (nb, Di, w["dtr"] + 2 * w["N"]),
                        pre + "mamba.dt_proj": (nb, w["dtr"], Di),
                        pre + "mamba.out_proj": (nb, Di, D)})
        out.update({pre + "mlp.w_gate": (nb, D, w["F"]),
                    pre + "mlp.w_up": (nb, D, w["F"]),
                    pre + "mlp.w_down": (nb, w["F"], D)})
    return out


def other_shapes(w: dict) -> dict[str, tuple[tuple, str]]:
    """Shape and kind of every served leaf that is not a projection."""
    D, Di, N, nb = w["D"], w["Di"], w["N"], w["L"] // w["P"]
    out = {"final_norm": ((D,), "norm")}
    for j in range(w["P"]):
        pre = f"blocks.{j}."
        out[pre + "norm1"] = ((nb, D), "norm")
        out[pre + "norm2"] = ((nb, D), "norm")
        if j != w["off"]:
            m = pre + "mamba."
            out.update({m + "conv_w": ((nb, w["K"], Di), "conv_w"),
                        m + "conv_b": ((nb, Di), "conv_b"),
                        m + "dt_norm": ((nb, w["dtr"]), "norm"),
                        m + "b_norm": ((nb, N), "norm"),
                        m + "c_norm": ((nb, N), "norm"),
                        m + "dt_bias": ((nb, Di), "dt_bias"),
                        m + "A_log": ((nb, Di, N), "A_log"),
                        m + "D": ((nb, Di), "ones")})
    return out


# leaves kept in float32, as the program initialises them; the rest in
# the served dtype
F32 = ("norm", "A_log", "ones")


def make_params(cfg: dict, seed: int) -> dict:
    """The served parameter dict for `cfg`, made on the default device."""
    w = served_widths(cfg)
    key = jax.random.PRNGKey(jax_seed(seed, "weights"))
    return _make_jit(tuple(sorted(w.items())), key)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_jit(widths: tuple, key):
    w = dict(widths)
    dt = jnp.bfloat16 if w["item"] == 2 else jnp.float32
    proj, other = projection_shapes(w), other_shapes(w)
    names = sorted(proj) + sorted(other) + ["embed"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    p = {"embed": (jax.random.normal(keys["embed"], (w["Vp"], w["D"]),
                                     jnp.float32) * EMBED_STD).astype(dt)}
    for name, (nb, K, N) in proj.items():
        p.update(projection(keys[name], name, (nb,), K, N, None))
    for name, (shape, kind) in other.items():
        k = keys[name]
        if kind == "norm":
            a = 1.0 + NORM_JITTER * jax.random.normal(k, shape, jnp.float32)
        elif kind == "conv_w":
            a = jax.random.normal(k, shape, jnp.float32) * w["K"] ** -0.5
        elif kind == "conv_b":
            a = jax.random.normal(k, shape, jnp.float32) * CONV_B_STD
        elif kind == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            d = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                        + math.log(DT_MIN))
            a = d + jnp.log(-jnp.expm1(-d))       # softplus^-1(d)
        elif kind == "A_log":
            a = jnp.broadcast_to(
                jnp.log(jnp.arange(1, w["N"] + 1, dtype=jnp.float32)), shape)
        else:
            a = jnp.ones(shape, jnp.float32)
        p[name] = a if kind in F32 else a.astype(dt)
    return p


# --------------------------------------------------------------- arena
def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V one cached position holds over the attention
    layers (one merged KVh * dh row each)."""
    w = served_widths(cfg)
    return 2 * w["n_attn"] * w["KVh"] * w["dh"] * w["item"]


def state_bytes(cfg: dict) -> int:
    """Bytes of one slot's Mamba state over all Mamba layers: h (Di, N)
    float32 and the conv's last K - 1 inputs (Di each)."""
    w = served_widths(cfg)
    return w["n_mamba"] * (w["Di"] * w["N"] * 4
                           + (w["K"] - 1) * w["Di"] * w["item"])


def slot_bytes(cfg: dict, rows: int) -> int:
    """`rows` K/V rows of the attention layers, and the Mamba state."""
    return rows * kv_row_bytes(cfg) + state_bytes(cfg)


# -------------------------------------------------------------- counts
def matmul_params(w: dict) -> dict:
    """Weights that multiply each token: the blocks' projections, and the
    head (the tied embedding, read in place as (Vp, D))."""
    return {"layers": sum(nb * K * N for nb, K, N
                          in projection_shapes(w).values()),
            "head": w["Vp"] * w["D"]}


@functools.lru_cache(maxsize=None)
def _leaf_bytes(widths: tuple) -> tuple[int, int]:
    """(projections and head, every other leaf): bytes of the served
    leaves `make_params` makes, split by name."""
    w = dict(widths)
    shapes = jax.eval_shape(functools.partial(_make_jit, widths),
                            jax.random.PRNGKey(0))
    proj = set(projection_shapes(w)) | {"embed"}
    out = [0, 0]
    for name, a in shapes.items():
        out[name not in proj] += a.size * a.dtype.itemsize
    return out[0], out[1]


def step_weight_bytes(w: dict) -> int:
    """Bytes one decode step reads under the `proj` scope: the
    projections and the head."""
    return _leaf_bytes(tuple(sorted(w.items())))[0]


def other_bytes(w: dict) -> int:
    """Bytes of the served leaves that are not projections (norms, conv,
    dt bias, A_log, D), read once a step besides them."""
    return _leaf_bytes(tuple(sorted(w.items())))[1]


def count_flops(w: dict, tokens: int, context_sum: int) -> float:
    mp = matmul_params(w)
    return (2.0 * (mp["layers"] + mp["head"]) * tokens
            + 4.0 * w["H"] * w["dh"] * w["n_attn"] * context_sum
            + 4.0 * w["Di"] * w["N"] * w["n_mamba"] * tokens)


def weight_bytes(ctx: dict) -> int:
    return step_weight_bytes(ctx["widths"])


def decode_flops(ctx: dict) -> float:
    return count_flops(ctx["widths"], ctx["stats"]["decode_tokens"],
                       flops.context_sum(ctx["requests"]))


def decode_bytes(ctx: dict) -> float:
    """Every weight once a step, each attended K/V row, and each decoded
    token's Mamba state read and written."""
    w, cfg, st = ctx["widths"], ctx["config"], ctx["stats"]
    return (float(step_weight_bytes(w) + other_bytes(w)) * st["decode_steps"]
            + float(kv_row_bytes(cfg)) * flops.context_sum(ctx["requests"])
            + 2.0 * state_bytes(cfg) * st["decode_tokens"])
