"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Every parameter carries a tuple of logical axis names (emitted by the model
init alongside the pytree). This module maps logical axes -> mesh axes,
checking divisibility and falling back to replication (recorded, never
silent) when a dim does not divide.

Default layout on the production mesh (pod, data, model):
  batch          -> (pod, data)        DP across pods and the data axis
  vocab*, heads, mlp, experts, ...     TP/EP on `model`
  embed          -> (pod, data) iff fsdp=True   (FSDP: params + opt state
                    sharded over the data axes; mandatory for >=100B archs)
  seq            -> (pod, data) for long-context decode (SP)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "vocab": "model",
    "vocab_out": "model",
    "embed": None,               # -> ("pod", "data") when fsdp
    "q_heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "experts_router": "model",
    "expert_mlp": None,
    "mamba_inner": "model",
    "mamba_inner2": "model",
    "mamba_state": None,
    "mamba_lowrank": None,
    "mamba_lowrank_dt": None,
    "rwkv_heads": "model",
    "rwkv_ffn": "model",
    "lora": None,
    "layers": None,
    "conv_k": None,
    "codebooks": None,
    "mix5": None,
    "mix2": None,
}


@dataclasses.dataclass
class ShardingPlan:
    mesh: Mesh
    rules: dict[str, Any]
    fallbacks: list[tuple[str, str, int]]  # (param, axis, dim) replicated

    def spec_for(self, name: str, logical: tuple[str, ...],
                 shape: tuple[int, ...]) -> P:
        parts = []
        used = set()
        for ax_name, dim in zip(logical, shape):
            mesh_ax = self.rules.get(ax_name)
            if mesh_ax is None:
                parts.append(None)
                continue
            axes = (mesh_ax,) if isinstance(mesh_ax, str) else tuple(mesh_ax)
            axes = tuple(a for a in axes if a in self.mesh.shape)
            size = int(np.prod([self.mesh.shape[a] for a in axes])) if axes \
                else 1
            if size <= 1 or dim % size != 0 or any(a in used for a in axes):
                if size > 1:
                    self.fallbacks.append((name, ax_name, dim))
                parts.append(None)
                continue
            used.update(axes)
            parts.append(axes[0] if len(axes) == 1 else axes)
        return P(*parts)

    def shardings(self, params_axes: dict[str, tuple],
                  shapes: dict[str, tuple]) -> dict[str, NamedSharding]:
        return {
            name: NamedSharding(self.mesh,
                                self.spec_for(name, ax, shapes[name]))
            for name, ax in params_axes.items()
        }


def make_plan(mesh: Mesh, *, fsdp: bool = False,
              overrides: Optional[dict] = None,
              mode: str = "tp") -> ShardingPlan:
    """mode:
      'tp'   — the baseline layout: DP over (pod, data), TP/EP on `model`
               (+ FSDP over the DP axes when fsdp=True).
      'zero' — pure data parallelism with ZeRO param sharding: batch over
               EVERY mesh axis, params/grads/opt-state sharded over
               (data, model) on their embed/vocab axis, no tensor
               parallelism. The right regime for <=15B dense models where
               TP-16 activation all-reduces dominate the roofline (§Perf).
    """
    rules = dict(DEFAULT_RULES)
    overrides = dict(overrides or {})
    # per-arch knobs that are not axis rules
    overrides.pop("base_optimizer", None)
    if overrides.pop("fsdp", False):
        fsdp = True
    mode = overrides.pop("mode", mode)
    if "experts_axis" in overrides:
        rules["experts"] = overrides.pop("experts_axis")
    if "expert_mlp_axis" in overrides:
        rules["expert_mlp"] = overrides.pop("expert_mlp_axis")
    rules.update(overrides)
    if mode == "zero":
        all_axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.shape)
        zero_axes = tuple(a for a in ("data", "model") if a in mesh.shape)
        for k in rules:
            rules[k] = None
        rules["batch"] = all_axes
        rules["embed"] = zero_axes
        rules["vocab"] = zero_axes
        rules["vocab_out"] = None
    elif fsdp:
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        rules["embed"] = dp_axes
    rules.setdefault("batch", ("pod", "data"))
    return ShardingPlan(mesh=mesh, rules=rules, fallbacks=[])


def serving_axes_for(name: str, params_axes: dict[str, tuple]
                     ) -> Optional[tuple]:
    """Logical axes for a *served* param key.

    `core.subnet.servable_params` rewrites each compressed weight `<w>`
    into derived keys the model init never named:

      <w>.codes      int8/int16 codes, same rank/layout as <w>
      <w>.packed{b}  int32 K-packed words — K shrinks to ceil(K/cpw) but
                     the axis ORDER is unchanged, so <w>'s logical axes
                     still label it (spec_for's divisibility check then
                     decides per packed shape whether the word count still
                     divides the mesh)
      <w>.scale      per-tensor scale, scalar or (layers,) when stacked

    Dense keys pass through; unknown keys return None (replicate)."""
    if name in params_axes:
        return params_axes[name]
    base, _, suffix = name.rpartition(".")
    ax = params_axes.get(base)
    if ax is None:
        return None
    if suffix == "codes" or (suffix.startswith("packed")
                             and suffix[len("packed"):].isdigit()):
        return ax
    if suffix == "scale":
        return ("layers",)      # (layers,) when stacked; rank-0 replicates
    return None


def serving_param_specs(plan: ShardingPlan, params_axes: dict[str, tuple],
                        params: dict) -> dict[str, P]:
    """PartitionSpecs for an engine's served param dict (dense weights,
    int codes, packed word streams, scales — DESIGN.md §4.12).

    Anything whose logical axes can't be recovered (or whose rank no
    longer matches, e.g. a per-tensor scalar scale) replicates; every
    genuinely TP-shardable axis (q/kv heads, mlp hidden, vocab_out) goes
    through the same `spec_for` divisibility-checked rules training uses,
    so a pruned width that stops dividing the mesh falls back to
    replication instead of crashing — recorded in `plan.fallbacks`.

    A row-parallel projection (o-proj, down-proj: a sharded contraction
    axis feeding the residual `embed`) shards its output columns
    instead. Split along K, the dot's partial sums would meet in an
    all-reduce (in the activation dtype, in an order no single device
    shares), and tp>1 decode would drift from tp=1; split along N, every
    output element is one device's full-K dot, and only exact
    all-gathers move activations."""
    specs = {}
    for name, leaf in params.items():
        ax = serving_axes_for(name, params_axes)
        if ax is None or len(ax) != np.ndim(leaf):
            specs[name] = P()
            continue
        spec = plan.spec_for(name, tuple(ax), np.shape(leaf))
        if len(ax) >= 2 and ax[-1] == "embed" and spec[-2] is not None \
                and spec[-1] is None:
            mesh_ax = spec[-2]
            names = (mesh_ax,) if isinstance(mesh_ax, str) else mesh_ax
            size = int(np.prod([plan.mesh.shape[a] for a in names]))
            spec = P(*spec[:-2], None,
                     mesh_ax if np.shape(leaf)[-1] % size == 0 else None)
        specs[name] = spec
    return specs


def kv_cache_specs(mesh: Mesh, cache_shapes: dict[str, tuple],
                   kv_heads: Optional[dict[str, int]] = None
                   ) -> dict[str, P]:
    """PartitionSpecs for an engine KV arena, contiguous or paged.

    Attention K/V leaves shard their KV heads over `model`. A contiguous
    arena stores them merged, (nb, B, S, KVh * dh): its last axis shards
    when `kv_heads[name]` (`LM.kv_heads`) divides the mesh, which splits
    it at whole-head boundaries. The paged (nb, n_pages, P, KVh, dh)
    pools shard axis 3, and likewise the paged per-row scale planes
    (nb, n_pages, P, KVh). The page/slot/row axes are never split: page
    tables stay host-side and every logical page maps to one local tile
    per device. A KVh that doesn't divide the mesh replicates (GQA smoke
    configs with 2 kv heads on 4 devices); recurrent-state leaves (mamba
    h/conv, rwkv shift/wkv) are O(1)-per-slot and replicate."""
    size = int(mesh.shape.get("model", 1))
    heads = kv_heads or {}
    specs: dict[str, P] = {}
    for name, shape in cache_shapes.items():
        kv = name.endswith(".k") or name.endswith(".v")
        sc = name.endswith("_scale")
        if kv and len(shape) == 4:
            split = heads.get(name, 1) % size == 0
        else:
            split = ((kv and len(shape) == 5) or (sc and len(shape) == 4)) \
                and shape[3] % size == 0
        specs[name] = P(None, None, None, "model") if size > 1 and split \
            else P()
    return specs


def batch_spec(mesh: Mesh, *, shard_seq: bool = False,
               mode: str = "tp") -> P:
    axes = ("pod", "data") if mode != "zero" else ("pod", "data", "model")
    dp = tuple(a for a in axes if a in mesh.shape)
    dp = dp[0] if len(dp) == 1 else dp
    if shard_seq:
        return P(None, dp)      # (batch, seq): SP for long-context
    return P(dp)


def constrain(x: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
