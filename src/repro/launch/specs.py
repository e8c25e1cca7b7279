"""ShapeDtypeStruct input stand-ins for every (arch x shape) cell.

`input_specs` is the shannon/kernels pattern: weak-type-correct, shardable,
no device allocation — the dry-run lowers against these.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import batch_spec
from repro.models.transformer import LM


def _sds(shape, dtype, mesh: Optional[Mesh], spec: Optional[P]):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                mesh: Optional[Mesh] = None, mode: str = "tp") -> dict:
    """Training/prefill batch stand-ins (the stub modality frontends provide
    token frames / patch embeddings here)."""
    B, S = shape.global_batch, shape.seq_len
    dp = batch_spec(mesh, shard_seq=False, mode=mode) \
        if mesh is not None else None
    out = {}
    if cfg.family == "audio":
        out["tokens"] = _sds((B, S, cfg.num_codebooks), jnp.int32, mesh, dp)
    elif cfg.family == "vlm":
        out["tokens"] = _sds((B, S - cfg.vision_patches), jnp.int32, mesh, dp)
        out["vision_embeds"] = _sds(
            (B, cfg.vision_patches, cfg.d_model),
            jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
            mesh, dp)
    else:
        out["tokens"] = _sds((B, S), jnp.int32, mesh, dp)
    return out


def decode_specs(cfg: ModelConfig, shape: ShapeConfig,
                 mesh: Optional[Mesh] = None,
                 cache_layout: str = "heads") -> dict:
    """serve_step stand-ins: one new token against a seq_len KV cache."""
    B, S = shape.global_batch, shape.seq_len
    lm = LM(cfg)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    cache_shapes = jax.eval_shape(lambda: lm.init_cache(B, S, dtype=dt))
    shard_seq = B == 1          # long-context: SP over the cache sequence
    dp_axes = tuple(a for a in ("pod", "data")
                    if mesh is not None and a in mesh.shape)
    dp = dp_axes[0] if len(dp_axes) == 1 else (dp_axes or None)

    model_size = mesh.shape.get("model", 1) if mesh is not None else 1

    def cache_spec(name, s):
        if mesh is None:
            return jax.ShapeDtypeStruct(s.shape, s.dtype)
        # (n_blocks, B, S, KV*dh) attn kv / (n_blocks, B, ...) states
        if ".k" in name or ".v" in name:
            if cache_layout == "seq":
                # shard the cache SEQUENCE on the model axis: attention
                # reduces over seq, so only the tiny softmax statistics
                # and the (B,1,H,dh) output cross devices (§Perf It.5)
                parts = [None, dp, "model", None]
                if shard_seq:
                    parts = [None, None, ("data", "model"), None]
                return _sds(s.shape, s.dtype, mesh, P(*parts))
            # TP the cache over its merged KV*dh axis: at whole KV heads
            # when they divide the axis, else within heads (d_head is
            # always 128 = 8x16) — a replicated 32k cache costs 13-26
            # GB/device on the large archs.
            kv_part = "model" if s.shape[3] % model_size == 0 else None
            parts = [None, dp, None, kv_part]
            if shard_seq:
                parts = [None, None, dp, kv_part]
            return _sds(s.shape, s.dtype, mesh, P(*parts))
        parts = [None] + [dp] + [None] * (len(s.shape) - 2)
        if shard_seq:
            parts = [None] * len(s.shape)
        return _sds(s.shape, s.dtype, mesh, P(*parts))

    caches = {k: cache_spec(k, v) for k, v in cache_shapes.items()}
    tok_shape = (B, 1, cfg.num_codebooks) if cfg.num_codebooks else (B, 1)
    token = _sds(tok_shape, jnp.int32, mesh,
                 P(dp) if (mesh is not None and not shard_seq) else P())
    pos = _sds((), jnp.int32, mesh, P())
    return {"caches": caches, "token": token, "pos": pos}


def param_specs(lm: LM, mesh: Optional[Mesh], plan=None,
                seed: int = 0) -> tuple[dict, dict, dict]:
    """(param ShapeDtypeStructs, their shardings, logical axes) without
    allocating anything. The logical-axes dict is static Python data built
    during the abstract trace, captured via closure."""
    captured: dict = {}

    def only_params(k):
        p, a = lm.init(k)
        captured.update(a)
        return p

    params_shapes = jax.eval_shape(only_params, jax.random.PRNGKey(seed))
    axes = captured
    if mesh is None or plan is None:
        return params_shapes, {k: None for k in params_shapes}, axes
    shardings = plan.shardings(
        axes, {k: v.shape for k, v in params_shapes.items()})
    with_sh = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings[k])
        for k, v in params_shapes.items()
    }
    return with_sh, shardings, axes
