"""InternLM2 (arXiv:2403.17297) as the benchmark serves it: a dense GQA
decoder with SwiGLU MLPs and an untied head, at the published widths or
at those a GETA artifact (the configuration's `artifact` group) leaves.

An architecture module is found by the configuration's `model_type`
(`bench/arch/<model_type>.py`) and gives the harness and the metric
readers everything that depends on the architecture:

  served_widths(cfg)        the widths the served weights have
  program_config(cfg)       the program's `ModelConfig`
  slim_plan(cfg, lm)        the artifact's `SlimPlan`, or None
  make_params(cfg, seed)    the served weights, on the device, one jit
  slot_bytes(cfg, rows)     device bytes of one arena slot at `rows` rows
  kv_row_bytes(cfg)         bytes one attended cache row holds
  weight_bytes(ctx)         bytes one decode step reads from the weights
  decode_flops(ctx)         model FLOPs of the run's decode tokens
  decode_bytes(ctx)         least bytes the run's decode steps move

`ctx` is a reader's context: `config`, `widths`, the engine's counters
(`stats`) and the run's `requests`.

The served weights follow the program's served-parameter convention (the
checkpoint format the engine loads); a packed artifact serves each
projection and the head as `benchlib.weights.projection` packs them:

  embed (Vp, D), head (D, Vp), final_norm (D,)
  blocks.0.norm1 / norm2 (L, D)
  blocks.0.attn.wq (L, D, H*dh), wk / wv (L, D, KVh*dh), wo (L, H*dh, D)
  blocks.0.mlp.w_gate / w_up (L, D, F), w_down (L, F, D)

Counts are model FLOPs only: the embedding lookup is a gather and counts
nothing, idle slots count nothing, and nothing recomputed counts. Bytes
are the least a step has to move: every weight once per decode step, and
the cached K/V rows each active request attends to.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchlib import flops
from benchlib.weights import jax_seed, projection, vocab_padded

EMBED_STD = 0.02
NORM_JITTER = 0.05


def served_widths(cfg: dict) -> dict:
    """Widths the served weights have: the published ones, or those the
    configuration's artifact leaves after pruning."""
    art = cfg.get("artifact", {})
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "dh": cfg["head_dim"],
        "Vp": vocab_padded(cfg["vocab_size"]),
        "H": art.get("num_attention_heads", cfg["num_attention_heads"]),
        "KVh": art.get("num_key_value_heads", cfg["num_key_value_heads"]),
        "F": art.get("intermediate_size", cfg["intermediate_size"]),
        "bits": art.get("bits"),
    }


# ------------------------------------------------------------- program
def program_config(cfg: dict):
    """The program's model config for the configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


def slim_plan(cfg: dict, lm):
    """The widths a pruned artifact serves at, for every layer of `lm`,
    so that prefill, decode and the arena run at them; None without an
    artifact."""
    art = cfg.get("artifact")
    if not art:
        return None
    from repro.core.subnet import SlimPlan
    from repro.models.layers import LayerShapes
    shp = dataclasses.replace(
        LayerShapes.from_config(lm.cfg),
        n_heads=art["num_attention_heads"],
        n_kv_heads=art["num_key_value_heads"],
        d_ff=art["intermediate_size"])
    return SlimPlan(layer_shapes=[shp for _ in lm.plan], kept_units={},
                    sparsity=float(art["sparsity"]))


# ------------------------------------------------------------- weights
def projection_shapes(w: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of each per-layer projection, and of the head."""
    D, H, KVh, dh, F = (w[k] for k in ("D", "H", "KVh", "dh", "F"))
    shp = {
        "attn.wq": (D, H * dh), "attn.wk": (D, KVh * dh),
        "attn.wv": (D, KVh * dh), "attn.wo": (H * dh, D),
        "mlp.w_gate": (D, F), "mlp.w_up": (D, F), "mlp.w_down": (F, D),
    }
    out = {f"blocks.0.{k}": v for k, v in shp.items()}
    out["head"] = (D, w["Vp"])
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """The served parameter dict for `cfg`, made on the default device."""
    w = served_widths(cfg)
    key = jax.random.PRNGKey(jax_seed(seed, "weights"))
    return _make_jit(tuple(sorted(w.items())), key)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_jit(widths: tuple, key):
    w = dict(widths)
    L, D, Vp = w["L"], w["D"], w["Vp"]
    shapes = projection_shapes(w)
    names = sorted(shapes) + ["embed", "final_norm", "blocks.0.norm1",
                              "blocks.0.norm2"]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    p = {
        "embed": (jax.random.normal(keys["embed"], (Vp, D), jnp.float32)
                  * EMBED_STD).astype(jnp.bfloat16),
        "final_norm": 1.0 + NORM_JITTER * jax.random.normal(
            keys["final_norm"], (D,), jnp.float32),
    }
    for n in ("blocks.0.norm1", "blocks.0.norm2"):
        p[n] = 1.0 + NORM_JITTER * jax.random.normal(keys[n], (L, D),
                                                     jnp.float32)
    for name, (K, N) in shapes.items():
        lead = () if name == "head" else (L,)
        p.update(projection(keys[name], name, lead, K, N, w["bits"]))
    return p


# --------------------------------------------------------------- arena
def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V that one cached position holds over all layers:
    bf16, or with the engine's `kv_bits` the int codes (two 4-bit codes
    to a byte) and one float32 step per row and KV head."""
    w = served_widths(cfg)
    bits = cfg.get("engine", {}).get("kv_bits")
    per_head = 2 * w["dh"] if bits is None else w["dh"] * bits // 8 + 4
    return 2 * w["L"] * w["KVh"] * per_head


def slot_bytes(cfg: dict, rows: int) -> int:
    """Every layer caches every row: `rows` rows of K and V."""
    return rows * kv_row_bytes(cfg)


# -------------------------------------------------------------- counts
def matmul_params(w: dict) -> dict:
    """Weights that multiply each token: per layer (all layers), and the
    head."""
    shp = projection_shapes(w)
    per_layer = sum(k * n for name, (k, n) in shp.items() if name != "head")
    return {"layers": w["L"] * per_layer,
            "head": shp["head"][0] * shp["head"][1]}


def step_weight_bytes(w: dict) -> int:
    """Bytes one decode step reads from the served weights (the embedding
    rows it gathers are left out)."""
    mp = matmul_params(w)
    n = mp["layers"] + mp["head"]
    if w["bits"] is None:
        return 2 * n
    return n * w["bits"] // 8


def count_flops(w: dict, tokens: int, context_sum: int) -> float:
    """FLOPs of `tokens` decoded tokens that attend `context_sum` keys in
    all: 2 per weight per token, and 4 * q_dim per key per layer (scores
    and values)."""
    mp = matmul_params(w)
    q_dim = w["H"] * w["dh"]
    return (2.0 * (mp["layers"] + mp["head"]) * tokens
            + 4.0 * q_dim * w["L"] * context_sum)


def count_bytes(w: dict, steps: int, context_sum: int, row_bytes: int
                ) -> float:
    """Least bytes `steps` decode steps move: the weights once a step,
    and each attended cache row."""
    return float(step_weight_bytes(w)) * steps + float(row_bytes) \
        * context_sum


def weight_bytes(ctx: dict) -> int:
    return step_weight_bytes(ctx["widths"])


def decode_flops(ctx: dict) -> float:
    return count_flops(ctx["widths"], ctx["stats"]["decode_tokens"],
                       flops.context_sum(ctx["requests"]))


def decode_bytes(ctx: dict) -> float:
    return count_bytes(ctx["widths"], ctx["stats"]["decode_steps"],
                       flops.context_sum(ctx["requests"]),
                       kv_row_bytes(ctx["config"]))
