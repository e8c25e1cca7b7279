"""Served weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, in the type they are served in,
so that the reference reads nothing the program made. Names and layouts
follow the program's served-parameter convention (the checkpoint format
the engine loads):

  embed (Vp, D), head (D, Vp), final_norm (D,)
  blocks.0.norm1 / norm2 (L, D)
  blocks.0.attn.wq (L, D, H*dh), wk / wv (L, D, KVh*dh), wo (L, H*dh, D)
  blocks.0.mlp.w_gate / w_up (L, D, F), w_down (L, F, D)

A packed artifact replaces each projection `<w>` (and the head) with
`<w>.packed{bits}`: signed `bits`-wide codes packed along the input (K)
axis into int32 words, least significant field first, plus
`<w>.scale`, one step size per layer (a scalar for the head). Codes are
uniform over the symmetric range +-(2**(bits-1) - 1); the step is chosen
so that each weight has the spread of its bf16 twin (fan_in ** -0.5).
Vp is the vocabulary rounded up to a multiple of 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_JITTER = 0.05


def vocab_padded(vocab: int) -> int:
    return -(-vocab // 256) * 256


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


def projection_shapes(w: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of each per-layer projection, and of the head."""
    L, D, H, KVh, dh, F = (w[k] for k in ("L", "D", "H", "KVh", "dh", "F"))
    shp = {
        "attn.wq": (D, H * dh), "attn.wk": (D, KVh * dh),
        "attn.wv": (D, KVh * dh), "attn.wo": (H * dh, D),
        "mlp.w_gate": (D, F), "mlp.w_up": (D, F), "mlp.w_down": (F, D),
    }
    out = {f"blocks.0.{k}": v for k, v in shp.items()}
    out["head"] = (D, w["Vp"])
    return out


def kv_bytes_per_row(cfg: dict) -> int:
    """Bytes of K and V that one cached position holds over all layers:
    bf16, or with the engine's `kv_bits` the int codes (two 4-bit codes
    to a byte) and one float32 step per row and KV head."""
    w = served_widths(cfg)
    bits = cfg.get("engine", {}).get("kv_bits")
    per_head = 2 * w["dh"] if bits is None else w["dh"] * bits // 8 + 4
    return 2 * w["L"] * w["KVh"] * per_head


def pack(codes: jax.Array, bits: int) -> jax.Array:
    """Pack signed codes along axis -2 into int32 words."""
    cpw = 32 // bits
    K = codes.shape[-2]
    pad = (-K) % cpw
    if pad:
        widths = [(0, 0)] * codes.ndim
        widths[-2] = (0, pad)
        codes = jnp.pad(codes, widths)
    c = codes.astype(jnp.int32) & ((1 << bits) - 1)
    c = c.reshape(c.shape[:-2] + (c.shape[-2] // cpw, cpw, c.shape[-1]))
    shifts = (jnp.arange(cpw, dtype=jnp.int32) * bits)[:, None]
    return jnp.sum(c << shifts, axis=-2, dtype=jnp.int32)


def _code_std(bits: int) -> float:
    q = (1 << (bits - 1)) - 1
    vals = np.arange(-q, q + 1, dtype=np.float64)
    return float(np.sqrt(np.mean(vals ** 2)))


def make_params(cfg: dict, seed: int) -> dict:
    """The served parameter dict for `cfg`, made on the default device."""
    w = served_widths(cfg)
    key = jax.random.PRNGKey(jax_seed(seed, "weights"))
    return _make_jit(tuple(sorted(w.items())), key)


def jax_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for JAX drawn from the run's seed, which may not fit
    32 bits, and a tag that keeps the streams of different uses apart."""
    tags = [int(b) for b in tag.encode()]
    rng = np.random.default_rng([int(seed) % 2**64] + tags)
    return int(rng.integers(2**31 - 1))


@functools.partial(jax.jit, static_argnums=(0,))
def _make_jit(widths: tuple, key):
    w = dict(widths)
    L, D, Vp, bits = w["L"], w["D"], w["Vp"], w["bits"]
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
        std = K ** -0.5
        if bits is None:
            p[name] = (jax.random.normal(keys[name], lead + (K, N),
                                         jnp.float32) * std
                       ).astype(jnp.bfloat16)
            continue
        q = (1 << (bits - 1)) - 1
        codes = jax.random.randint(keys[name], lead + (K, N), -q, q + 1,
                                   jnp.int32)
        p[f"{name}.packed{bits}"] = pack(codes, bits)
        p[f"{name}.scale"] = jnp.full(lead, std / _code_std(bits),
                                      jnp.float32)
    return p
