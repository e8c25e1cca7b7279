"""Helpers every architecture module (`bench/arch/<model_type>.py`) uses
to make its served weights from the seed, on the device, in one jitted
call, in the type they are served in.

A packed projection `<w>` is served as `<w>.packed{bits}`: signed
`bits`-wide codes packed along the input (K) axis into int32 words, least
significant field first, plus `<w>.scale`, one step size per leading
index (a scalar where there is none). Codes are uniform over the
symmetric range +-(2**(bits-1) - 1); the step is chosen so that each
weight has the spread of its bf16 twin (fan_in ** -0.5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def vocab_padded(vocab: int) -> int:
    """The vocabulary rounded up to a multiple of 256."""
    return -(-vocab // 256) * 256


def jax_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for JAX drawn from the run's seed, which may not fit
    32 bits, and a tag that keeps the streams of different uses apart."""
    tags = [int(b) for b in tag.encode()]
    rng = np.random.default_rng([int(seed) % 2**64] + tags)
    return int(rng.integers(2**31 - 1))


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


def projection(key, name: str, lead: tuple, K: int, N: int,
               bits: int | None) -> dict:
    """The served leaves of one (lead..., K, N) projection `name`: bf16
    with spread K ** -0.5, or its packed codes and step sizes."""
    std = K ** -0.5
    if bits is None:
        return {name: (jax.random.normal(key, lead + (K, N), jnp.float32)
                       * std).astype(jnp.bfloat16)}
    q = (1 << (bits - 1)) - 1
    codes = jax.random.randint(key, lead + (K, N), -q, q + 1, jnp.int32)
    return {f"{name}.packed{bits}": pack(codes, bits),
            f"{name}.scale": jnp.full(lead, std / _code_std(bits),
                                      jnp.float32)}
