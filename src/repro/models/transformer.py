"""Unified decoder-LM assembly for every assigned architecture family.

Layer heterogeneity (hybrid attn:mamba interleave, MoE-every-k) is handled
by a *period plan*: the layer pattern repeats with period p, parameters are
stacked over n_blocks = L / p per position-in-period, and the layer stack is
a single lax.scan over n_blocks whose body unrolls the p sublayers. This
keeps the HLO small (compile time ~seconds at 512 devices) while supporting
Jamba-style 1:7 interleave and MoE-every-2.

Weight quantization keeps per-stack (d, q_m, t) granularity (DESIGN.md
§2.2). Sites on projections routed through `layers.dense_proj` fuse into
the GEMM's RHS tile load inside the block body (no quantized stack is ever
materialized); the rest (einsum weights, head/embed) are fake-quanted once
per stack *outside* the scan. Activation quantizers apply inside the body.

Dense/attention projections route through the kernel dispatch layer
(`repro.kernels.dispatch`, DESIGN.md §4) via `layers.dense_proj`. The same
entry point consumes compressed Subnet weights: a param dict may replace a
2-D weight `<name>` with `<name>.codes` (int8/int16 codes, scan-stacked
like the dense tensor) + `<name>.scale`, and the block body then decodes
through the quant-dequant GEMM epilogue — the `--compressed` serving path.
Sub-byte sites ride as `<name>.packed{bits}` int32 word streams instead
(the storage width stays static in the key) and decode through the
unpack-dequant epilogue — the `--packed` path (DESIGN.md §4.8).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.graph import FamilySpec, GraphBuilder
from repro.core.quant import QuantParams, fake_quant, init_quant_params
from repro.models import layers as Lyr
from repro.models.layers import qw


@dataclasses.dataclass(frozen=True)
class SubLayer:
    j: int
    mixer: str     # attn | mamba | rwkv
    ffn: str       # mlp | moe | chanmix | none


def layer_plan(cfg: ModelConfig) -> tuple[list[SubLayer], int]:
    """(per-period sublayer specs, n_blocks)."""
    if cfg.family == "ssm_rwkv":
        return [SubLayer(0, "rwkv", "chanmix")], cfg.n_layers
    period = 1
    if cfg.family == "hybrid":
        period = cfg.attn_every
    if cfg.moe is not None:
        period = int(_lcm(period, cfg.moe.every))
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    plan = []
    for j in range(period):
        if cfg.family == "hybrid":
            mixer = "attn" if j % cfg.attn_every == 0 else "mamba"
        else:
            mixer = "attn"
        if cfg.moe is not None and j % cfg.moe.every == cfg.moe.every - 1:
            ffn = "moe"
        else:
            ffn = "mlp"
        plan.append(SubLayer(j, mixer, ffn))
    return plan, cfg.n_layers // period


def _lcm(a, b):
    return a * b // math.gcd(a, b)


# Which params receive weight-quant sites (per sublayer component).
_QUANT_WEIGHTS = {
    "attn": ["wq", "wk", "wv", "wo"],
    "mlp": ["w_gate", "w_up", "w_down"],
    "moe": ["router", "we_gate", "we_up", "we_down"],
    "mamba": ["in_proj_x", "in_proj_z", "x_proj", "dt_proj", "out_proj"],
    "rwkv": ["wr", "wk", "wv", "wg", "wo", "decay_w1", "decay_w2"],
    "chanmix": ["cm_k", "cm_v", "cm_r"],
}
_ACT_SITES = {
    "attn": ["attn_out"],
    "mlp": ["mlp_act"],
    "moe": [],
    "mamba": ["mamba_out"],
    "rwkv": ["tm_out"],
    "chanmix": ["cm_act"],
}


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan, self.n_blocks = layer_plan(cfg)
        # Physical dims per position-in-period. The forward/prefill/decode
        # bodies and init_cache consume these instead of the global config,
        # so a pruned subnet (core.subnet.derive_slim_plan) executes — and
        # allocates KV — at its sliced widths. Per-stack pruning granularity
        # (DESIGN.md §2.2) means every layer of a stack shares its
        # position's shapes: the layer scan stays shape-homogeneous and the
        # compiled-shape set is bounded by the period.
        self.shapes: list[Lyr.LayerShapes] = [
            Lyr.LayerShapes.from_config(cfg) for _ in self.plan]
        self.slim_plan = None
        # Optional NamedSharding for the (B, S, D) residual stream. Without
        # this pin, GSPMD's fixed-point for the scan carry can settle on
        # (batch-replicated, D-model-sharded) — measured 16x activation
        # blow-up on the 398B configs. Set by launch/dryrun/train, and
        # replicated while a tensor-parallel engine traces its jits.
        self.act_sharding = None
        # Optional dict name -> NamedSharding: pins fake-quantized weights
        # to their parameter sharding so the f32 quantization chain runs at
        # shard-local width (GSPMD otherwise quantizes *after* the FSDP
        # all-gather — measured ~35 gathered f32 expert-weight copies).
        self.param_shardings = None

    def _constrain(self, x):
        if self.act_sharding is not None and x.ndim == 3:
            x = jax.lax.with_sharding_constraint(x, self.act_sharding)
        return x

    def apply_slim_plan(self, plan) -> None:
        """Execute at a `core.subnet.SlimPlan`'s physical widths.

        After this, forward/prefill/decode_step expect *sliced* params
        (`PruningSpace.materialize` output) and init_cache allocates the
        shrunk KV/state arena (surviving kv heads / mamba channels / rwkv
        heads only)."""
        if len(plan.layer_shapes) != len(self.plan):
            raise ValueError(
                f"slim plan has {len(plan.layer_shapes)} sublayer shapes, "
                f"model period has {len(self.plan)}")
        self.shapes = list(plan.layer_shapes)
        self.slim_plan = plan

    # ------------------------------------------------------------- params
    def init(self, key) -> tuple[dict, dict]:
        cfg = self.cfg
        dt = Lyr._dt(cfg)
        D, Vp = cfg.d_model, cfg.vocab_padded
        params: dict = {}
        axes: dict = {}
        keys = jax.random.split(key, 4 + len(self.plan) * 2)

        # embeddings
        if cfg.num_codebooks:
            params["embed"] = jax.random.normal(
                keys[0], (cfg.num_codebooks, Vp, D), dt) * 0.02
            axes["embed"] = ("codebooks", "vocab", "embed")
            params["head"] = jax.random.normal(
                keys[1], (D, cfg.num_codebooks * Vp), dt) * D ** -0.5
            axes["head"] = ("embed", "vocab_out")
        else:
            params["embed"] = jax.random.normal(keys[0], (Vp, D), dt) * 0.02
            axes["embed"] = ("vocab", "embed")
            if not cfg.tie_embeddings:
                params["head"] = jax.random.normal(
                    keys[1], (D, Vp), dt) * D ** -0.5
                axes["head"] = ("embed", "vocab_out")
        params["final_norm"] = jnp.ones((D,), jnp.float32)
        axes["final_norm"] = ("embed",)

        for i, sub in enumerate(self.plan):
            kmix, kffn = jax.random.split(keys[4 + i], 2)
            pre = f"blocks.{sub.j}"
            params[f"{pre}.norm1"] = jnp.ones((self.n_blocks, D), jnp.float32)
            axes[f"{pre}.norm1"] = ("layers", "embed")
            if sub.ffn != "none":
                params[f"{pre}.norm2"] = jnp.ones((self.n_blocks, D),
                                                  jnp.float32)
                axes[f"{pre}.norm2"] = ("layers", "embed")

            if sub.mixer == "attn":
                p, a = Lyr.init_attention(kmix, cfg, f"{pre}.attn",
                                          self.n_blocks, dt)
            elif sub.mixer == "mamba":
                p, a = Lyr.init_mamba(kmix, cfg, f"{pre}.mamba",
                                      self.n_blocks, dt)
                # split in_proj for clean pruning groups
                ip = p.pop(f"{pre}.mamba.in_proj")
                ax = a.pop(f"{pre}.mamba.in_proj")
                half = ip.shape[-1] // 2
                p[f"{pre}.mamba.in_proj_x"] = ip[..., :half]
                p[f"{pre}.mamba.in_proj_z"] = ip[..., half:]
                a[f"{pre}.mamba.in_proj_x"] = ax[:-1] + ("mamba_inner",)
                a[f"{pre}.mamba.in_proj_z"] = ax[:-1] + ("mamba_inner",)
            else:  # rwkv
                p, a = Lyr.init_rwkv(kmix, cfg, f"{pre}.rwkv",
                                     self.n_blocks, dt)
            params.update(p)
            axes.update(a)

            if sub.ffn == "mlp":
                p, a = Lyr.init_mlp(kffn, cfg, f"{pre}.mlp", self.n_blocks, dt)
                params.update(p)
                axes.update(a)
            elif sub.ffn == "moe":
                p, a = Lyr.init_moe(kffn, cfg, f"{pre}.moe", self.n_blocks, dt)
                params.update(p)
                axes.update(a)
        return params, axes

    # --------------------------------------------------------- quantization
    def quant_weight_names(self) -> list[str]:
        names = []
        for sub in self.plan:
            pre = f"blocks.{sub.j}"
            comp = sub.mixer
            names += [f"{pre}.{comp}.{w}" for w in _QUANT_WEIGHTS[comp]]
            if sub.ffn in ("mlp", "moe"):
                names += [f"{pre}.{sub.ffn}.{w}"
                          for w in _QUANT_WEIGHTS[sub.ffn]]
                if sub.ffn == "moe" and self.cfg.moe.shared_expert:
                    names += [f"{pre}.moe.shared.{w}"
                              for w in _QUANT_WEIGHTS["mlp"]]
            elif sub.ffn == "chanmix":
                names += [f"{pre}.rwkv.{w}" for w in _QUANT_WEIGHTS["chanmix"]]
        names.append("head" if not self.cfg.tie_embeddings
                     or self.cfg.num_codebooks else "embed")
        return names

    def act_site_names(self) -> list[str]:
        names = []
        for sub in self.plan:
            pre = f"blocks.{sub.j}"
            names += [f"{pre}.{sub.mixer}.{s}.aq"
                      for s in _ACT_SITES[sub.mixer]]
            if sub.ffn in ("mlp", "moe"):
                names += [f"{pre}.{sub.ffn}.{s}.aq"
                          for s in _ACT_SITES[sub.ffn]]
            elif sub.ffn == "chanmix":
                names += [f"{pre}.rwkv.{s}.aq" for s in _ACT_SITES["chanmix"]]
        return names

    def init_qparams(self, params: dict, bits_init: float = 8.0,
                     act_quant: bool = False) -> dict[str, QuantParams]:
        qp = {}
        for name in self.quant_weight_names():
            if name in params:
                qp[name + ".wq"] = init_quant_params(params[name],
                                                     bits=bits_init)
        if act_quant:
            for site in self.act_site_names():
                qp[site] = init_quant_params(q_m=4.0, bits=bits_init)
        return qp

    # Stacked 2-D projections of these components run through
    # `layers.dense_proj` inside the block body — their weight quantizer
    # fuses into the GEMM's RHS tile load (`fq_matmul_op`), so the stack
    # never materializes a quantized copy in HBM. Per-stack (d, q_m, t)
    # granularity is unchanged: the same scalars apply to every layer
    # slice (elementwise op commutes with the scan slicing).
    _FUSED_QAT_COMPONENTS = Lyr.ROUTED_COMPONENTS

    def _fused_qat_site(self, name: str, w) -> bool:
        parts = name.split(".")
        return (Lyr.kernel_dispatch_enabled() and name.startswith("blocks.")
                and len(parts) >= 3
                and parts[-2] in self._FUSED_QAT_COMPONENTS and w.ndim == 3)

    def _prequantize(self, params: dict, qparams: Optional[dict]
                     ) -> tuple[dict, Optional[dict]]:
        """Split weight quantizers into fused-in-body sites (routed 2-D
        projections, applied inside the GEMM epilogue by `dense_proj`) and
        prequantized stacks (einsum weights, head/embed — fake-quanted once
        outside the layer scan). Returns (params, body qparams)."""
        if qparams is None:
            return params, None
        out = dict(params)
        fused_q: dict = {}
        for name in self.quant_weight_names():
            site = name + ".wq"
            if name in out and site in qparams:
                if self._fused_qat_site(name, out[name]):
                    fused_q[site] = qparams[site]
                    continue
                q = qparams[site]
                w = fake_quant(out[name], q.d, q.q_m, q.t)
                if self.param_shardings is not None \
                        and name in self.param_shardings:
                    w = jax.lax.with_sharding_constraint(
                        w, self.param_shardings[name])
                out[name] = w
        body_q = {k: v for k, v in qparams.items() if k.endswith(".aq")}
        body_q.update(fused_q)
        return out, (body_q or None)

    # -------------------------------------------------------------- forward
    def _embed_tokens(self, params, tokens):
        cfg = self.cfg
        if cfg.num_codebooks:
            # tokens: (B, S, n_codebooks) -> sum of per-codebook embeddings
            embs = [params["embed"][c][tokens[..., c]]
                    for c in range(cfg.num_codebooks)]
            return sum(embs)
        return params["embed"][tokens]

    def _head(self, params, h):
        cfg = self.cfg
        if cfg.tie_embeddings and not cfg.num_codebooks:
            return h @ params["embed"].T
        return Lyr.dense_proj(h, params, None, "head")

    def _block_params(self, params: dict) -> dict:
        return {k: v for k, v in params.items() if k.startswith("blocks.")}

    def _body(self, qp_body, rope, window_rope=None):
        cfg = self.cfg

        def body(x, lp):
            x = self._constrain(x)
            for sub, shp in zip(self.plan, self.shapes):
                pre = f"blocks.{sub.j}"
                h = Lyr.rmsnorm(x, lp[f"{pre}.norm1"], cfg.norm_eps)
                if sub.mixer == "attn":
                    mix, _ = Lyr.attn_apply(
                        lp, qp_body, cfg, h, rope=rope, window=cfg.window,
                        prefix=f"{pre}.attn", shapes=shp)
                elif sub.mixer == "mamba":
                    mix, _ = Lyr.mamba_apply(lp, qp_body, cfg, h,
                                             prefix=f"{pre}.mamba", shapes=shp)
                else:
                    mix, _ = Lyr.rwkv_timemix_apply(lp, qp_body, cfg, h,
                                                    prefix=f"{pre}.rwkv",
                                                    shapes=shp)
                x = x + mix
                if sub.ffn == "none":
                    continue
                h2 = Lyr.rmsnorm(x, lp[f"{pre}.norm2"], cfg.norm_eps)
                if sub.ffn == "mlp":
                    f = Lyr.mlp_apply(lp, qp_body, cfg, h2, prefix=f"{pre}.mlp")
                elif sub.ffn == "moe":
                    f = Lyr.moe_apply(lp, qp_body, cfg, h2, prefix=f"{pre}.moe",
                                      shapes=shp)
                else:
                    f, _ = Lyr.rwkv_chanmix_apply(lp, qp_body, cfg, h2,
                                                  prefix=f"{pre}.rwkv")
                x = x + f
            return x, None

        return body

    def forward(self, params: dict, qparams: Optional[dict], tokens,
                vision_embeds=None):
        """tokens: (B, S[, n_codebooks]); vision_embeds: (B, P, D) for vlm.
        Returns logits (B, S_total, ...)."""
        cfg = self.cfg
        params, qp_body = self._prequantize(params, qparams)
        x = self._embed_tokens(params, tokens)
        if cfg.vision_patches and vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        x = self._constrain(x)
        S = x.shape[1]
        rope = Lyr.rope_tables(S, cfg.d_head, cfg.rope_theta)
        body = self._body(qp_body, rope)
        if cfg.remat:
            # full remat of the block body: only the per-layer residual
            # stream survives to the backward (measured 2x temp reduction
            # vs dots_with_no_batch_dims at 4k seq)
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        bp = self._block_params(params)
        if self.n_blocks <= 2:
            # unrolled: the roofline's depth-1/depth-2 differencing needs
            # per-layer costs visible to HloCostAnalysis (a while body is
            # visited once regardless of trip count)
            for i in range(self.n_blocks):
                x, _ = body(x, {k: v[i] for k, v in bp.items()})
        else:
            x, _ = jax.lax.scan(body, x, bp)
        x = self._constrain(x)
        x = Lyr.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)
        if cfg.num_codebooks:
            B, St = logits.shape[:2]
            logits = logits.reshape(B, St, cfg.num_codebooks, cfg.vocab_padded)
        return logits

    # ----------------------------------------------------------------- loss
    def loss(self, params, qparams, batch) -> jax.Array:
        """Next-token cross-entropy, vocab-shard friendly.

        The gold logit is extracted with an iota-compare masked reduction
        (fuses under GSPMD when the vocab axis is model-sharded) instead of
        take_along_axis, which would all-gather the full (B, S, V) logits —
        measured at +24 GB/device temp on the 92k-vocab archs."""
        cfg = self.cfg
        tokens = batch["tokens"]
        logits = self.forward(params, qparams, tokens,
                              vision_embeds=batch.get("vision_embeds"))
        if cfg.vision_patches:
            logits = logits[:, cfg.vision_patches:]
        pred = logits[:, :-1].astype(jnp.float32)
        tgt = tokens[:, 1:]
        logz = jax.nn.logsumexp(pred, axis=-1)
        vocab_iota = jnp.arange(pred.shape[-1], dtype=tgt.dtype)
        gold = jnp.sum(jnp.where(vocab_iota == tgt[..., None], pred, 0.0),
                       axis=-1)
        return jnp.mean(logz - gold)

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16):
        """Decode cache arena, sized from the per-sublayer shapes — a
        pruned subnet allocates KV rows for *surviving* kv heads (and
        mamba channels / rwkv heads) only, so the HBM the arena pins
        shrinks with realized sparsity, not just the weight bytes."""
        cfg = self.cfg
        caches = {}
        for sub, shp in zip(self.plan, self.shapes):
            pre = f"blocks.{sub.j}"
            nb = self.n_blocks
            if sub.mixer == "attn":
                S = min(max_seq, cfg.window) if cfg.window > 0 else max_seq
                caches[f"{pre}.k"] = jnp.zeros(
                    (nb, batch, S, shp.n_kv_heads, shp.d_head), dtype)
                caches[f"{pre}.v"] = jnp.zeros(
                    (nb, batch, S, shp.n_kv_heads, shp.d_head), dtype)
            elif sub.mixer == "mamba":
                Di = shp.mamba_inner
                caches[f"{pre}.h"] = jnp.zeros(
                    (nb, batch, Di, cfg.mamba.d_state), jnp.float32)
                caches[f"{pre}.conv"] = jnp.zeros(
                    (nb, batch, cfg.mamba.d_conv - 1, Di), dtype)
            else:  # rwkv
                D = shp.d_model
                H = shp.rwkv_heads
                dh = cfg.rwkv.head_size
                caches[f"{pre}.tm_shift"] = jnp.zeros((nb, batch, D),
                                                      jnp.float32)
                caches[f"{pre}.wkv"] = jnp.zeros((nb, batch, H, dh, dh),
                                                 jnp.float32)
                caches[f"{pre}.cm_shift"] = jnp.zeros((nb, batch, D),
                                                      jnp.float32)
        return caches

    def init_paged_cache(self, batch: int, n_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_bits: Optional[int] = None):
        """Paged decode arena: attention K/V become *pools* of
        (nb, n_pages, page_size, KVh, dh) pages shared by every slot and
        addressed through per-slot page tables (`Lyr.PagedView`), so HBM
        scales with written rows, not slots × max_seq. With `kv_bits`
        (8 | 4) the pools hold int8 codes (nibble pairs halve the byte
        width at 4) plus per-row f32 scale planes `<pre>.k_scale` /
        `<pre>.v_scale`, decoded in-kernel at read time. Recurrent state
        (mamba / rwkv) is O(1) per slot and stays a contiguous (nb,
        batch, ...) arena — only attention rows page. `batch` sizes those
        state leaves (= max_slots)."""
        cfg = self.cfg
        if cfg.window > 0:
            raise ValueError("paged KV arena needs full (non-ring) caches; "
                             f"window={cfg.window}")
        if kv_bits is not None:
            from repro.core.quant import KV_STORAGE_BITS
            if kv_bits not in KV_STORAGE_BITS:
                raise ValueError(f"kv_bits must be in {KV_STORAGE_BITS}, "
                                 f"got {kv_bits}")
        caches = {}
        for sub, shp in zip(self.plan, self.shapes):
            pre = f"blocks.{sub.j}"
            nb = self.n_blocks
            if sub.mixer == "attn":
                KVh, dh = shp.n_kv_heads, shp.d_head
                if kv_bits is None:
                    z = jnp.zeros((nb, n_pages, page_size, KVh, dh), dtype)
                    caches[f"{pre}.k"] = z
                    caches[f"{pre}.v"] = z
                else:
                    if kv_bits == 4 and dh % 2:
                        raise ValueError(f"kv_bits=4 packs code pairs; "
                                         f"d_head={dh} must be even")
                    dhs = dh // 2 if kv_bits == 4 else dh
                    zc = jnp.zeros((nb, n_pages, page_size, KVh, dhs),
                                   jnp.int8)
                    zs = jnp.zeros((nb, n_pages, page_size, KVh),
                                   jnp.float32)
                    caches[f"{pre}.k"] = zc
                    caches[f"{pre}.v"] = zc
                    caches[f"{pre}.k_scale"] = zs
                    caches[f"{pre}.v_scale"] = zs
            elif sub.mixer == "mamba":
                Di = shp.mamba_inner
                caches[f"{pre}.h"] = jnp.zeros(
                    (nb, batch, Di, cfg.mamba.d_state), jnp.float32)
                caches[f"{pre}.conv"] = jnp.zeros(
                    (nb, batch, cfg.mamba.d_conv - 1, Di), dtype)
            else:  # rwkv
                D = shp.d_model
                H = shp.rwkv_heads
                dh = cfg.rwkv.head_size
                caches[f"{pre}.tm_shift"] = jnp.zeros((nb, batch, D),
                                                      jnp.float32)
                caches[f"{pre}.wkv"] = jnp.zeros((nb, batch, H, dh, dh),
                                                 jnp.float32)
                caches[f"{pre}.cm_shift"] = jnp.zeros((nb, batch, D),
                                                      jnp.float32)
        return caches

    def decode_step(self, params: dict, qparams: Optional[dict], caches: dict,
                    token, pos, pages=None):
        """One-token decode. token: (B, 1[, n_codebooks]); pos: scalar
        (static batching, every sequence in lockstep) or (B,) int vector
        (continuous batching: each slot at its own absolute position).
        `pages` (a `Lyr.PagedView`) switches attention caches to the
        paged pools of `init_paged_cache` — the view's table indirects
        every K/V write and read; recurrent state is untouched.
        Returns (logits, new_caches)."""
        cfg = self.cfg
        params, qp_body = self._prequantize(params, qparams)
        x = self._embed_tokens(params, token)
        B = x.shape[0]
        # rope at each sequence's absolute position
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        posf = pos.astype(jnp.float32)
        freqs = cfg.rope_theta ** (-jnp.arange(0, cfg.d_head, 2,
                                               dtype=jnp.float32) / cfg.d_head)
        ang = posf[:, None] * freqs[None, :]
        rope = (jnp.cos(ang)[:, None], jnp.sin(ang)[:, None])   # (B, 1, dh/2)

        def body(x, inp):
            x = self._constrain(x)
            lp = inp["p"]
            cc = inp["c"]
            new_c = {}
            for sub, shp in zip(self.plan, self.shapes):
                pre = f"blocks.{sub.j}"
                h = Lyr.rmsnorm(x, lp[f"{pre}.norm1"], cfg.norm_eps)
                if sub.mixer == "attn" and pages is not None:
                    cache = (cc[f"{pre}.k"], cc[f"{pre}.v"], pos)
                    if pages.kv_bits is not None:
                        cache += (cc[f"{pre}.k_scale"], cc[f"{pre}.v_scale"])
                    mix, nc = Lyr.attn_apply(
                        lp, qp_body, cfg, h, rope=rope, window=cfg.window,
                        prefix=f"{pre}.attn", shapes=shp, cache=cache,
                        pages=pages)
                    new_c[f"{pre}.k"], new_c[f"{pre}.v"], _, nks, nvs = nc
                    if pages.kv_bits is not None:
                        new_c[f"{pre}.k_scale"] = nks
                        new_c[f"{pre}.v_scale"] = nvs
                elif sub.mixer == "attn":
                    mix, nc = Lyr.attn_apply(
                        lp, qp_body, cfg, h, rope=rope, window=cfg.window,
                        prefix=f"{pre}.attn", shapes=shp,
                        cache=(cc[f"{pre}.k"], cc[f"{pre}.v"], pos))
                    new_c[f"{pre}.k"], new_c[f"{pre}.v"], _ = nc
                elif sub.mixer == "mamba":
                    mix, ns = Lyr.mamba_apply(
                        lp, qp_body, cfg, h, prefix=f"{pre}.mamba", shapes=shp,
                        state=(cc[f"{pre}.h"], cc[f"{pre}.conv"]))
                    new_c[f"{pre}.h"], new_c[f"{pre}.conv"] = ns
                else:
                    mix, ns = Lyr.rwkv_timemix_apply(
                        lp, qp_body, cfg, h, prefix=f"{pre}.rwkv", shapes=shp,
                        state=(cc[f"{pre}.tm_shift"], cc[f"{pre}.wkv"]))
                    new_c[f"{pre}.tm_shift"], new_c[f"{pre}.wkv"] = ns
                x = self._constrain(x + mix)
                if sub.ffn == "none":
                    continue
                h2 = Lyr.rmsnorm(x, lp[f"{pre}.norm2"], cfg.norm_eps)
                if sub.ffn == "mlp":
                    f = Lyr.mlp_apply(lp, qp_body, cfg, h2, prefix=f"{pre}.mlp")
                elif sub.ffn == "moe":
                    f = Lyr.moe_apply(lp, qp_body, cfg, h2, prefix=f"{pre}.moe",
                                      shapes=shp)
                else:
                    f, ns = Lyr.rwkv_chanmix_apply(
                        lp, qp_body, cfg, h2, prefix=f"{pre}.rwkv",
                        state=cc[f"{pre}.cm_shift"])
                    new_c[f"{pre}.cm_shift"] = ns
                x = self._constrain(x + f)
            return x, new_c

        bp = self._block_params(params)
        if self.n_blocks <= 2:
            new_list = []
            for i in range(self.n_blocks):
                x, nc = body(x, {"p": {k: v[i] for k, v in bp.items()},
                                 "c": {k: v[i] for k, v in caches.items()}})
                new_list.append(nc)
            new_caches = {k: jnp.stack([nc[k] for nc in new_list])
                          for k in new_list[0]}
        else:
            x, new_caches = jax.lax.scan(body, x, {"p": bp, "c": caches})
        x = Lyr.rmsnorm(self._constrain(x), params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)
        if cfg.num_codebooks:
            B = logits.shape[0]
            logits = logits.reshape(B, 1, cfg.num_codebooks, cfg.vocab_padded)
        return logits, new_caches

    def verify_chunk(self, params: dict, qparams: Optional[dict],
                     caches: dict, tokens, pos,
                     last_logit_only: bool = False):
        """Score a T-token chunk mid-sequence against the live caches —
        the speculative verify pass. tokens: (B, T) where column 0 is the
        last committed token of each slot and columns 1..T-1 are draft
        proposals; pos: (B,) absolute position of column 0. One batched
        pass writes K/V rows [pos, pos+T) per slot and returns logits for
        all T positions (a leading-match acceptance rule then commits the
        argmaxes) — T target decode steps for the price of one dispatch,
        the same GEMM-shaping win one-shot prefill gets at admission.

        Attention-mixer layers only: mamba/rwkv carry a recurrent state
        that a rejected suffix cannot roll back (KV rows can be zeroed;
        an SSM state cannot be un-stepped). Full (window == 0) arenas
        only, for the same reason — ring wrap overwrites history.
        Returns (logits (B, T, V), new_caches); `last_logit_only` projects
        just the final position through the head, like prefill's — the
        engine's chunked prefill only feeds on the last chunk's last
        position, so every earlier head GEMM would be dead work."""
        cfg = self.cfg
        bad = [sub.mixer for sub in self.plan if sub.mixer != "attn"]
        if bad:
            raise ValueError(
                f"verify_chunk needs attention mixers everywhere (rollback "
                f"zeroes KV rows); plan has {sorted(set(bad))} layers whose "
                f"recurrent state cannot be rolled back")
        if cfg.num_codebooks:
            raise ValueError("verify_chunk serves plain token LMs")
        params, qp_body = self._prequantize(params, qparams)
        x = self._embed_tokens(params, tokens)
        B, T = x.shape[0], x.shape[1]
        # rope at each slot's absolute positions pos[b] + [0, T)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        posf = (pos[:, None] + jnp.arange(T)[None, :]).astype(jnp.float32)
        freqs = cfg.rope_theta ** (-jnp.arange(0, cfg.d_head, 2,
                                               dtype=jnp.float32) / cfg.d_head)
        ang = posf[..., None] * freqs[None, None, :]
        rope = (jnp.cos(ang), jnp.sin(ang))                 # (B, T, dh/2)

        def body(x, inp):
            x = self._constrain(x)
            lp = inp["p"]
            cc = inp["c"]
            new_c = {}
            for sub, shp in zip(self.plan, self.shapes):
                pre = f"blocks.{sub.j}"
                h = Lyr.rmsnorm(x, lp[f"{pre}.norm1"], cfg.norm_eps)
                mix, nc = Lyr.attn_apply(
                    lp, qp_body, cfg, h, rope=rope, window=cfg.window,
                    prefix=f"{pre}.attn", shapes=shp, chunked=True,
                    cache=(cc[f"{pre}.k"], cc[f"{pre}.v"], pos))
                new_c[f"{pre}.k"], new_c[f"{pre}.v"], _ = nc
                x = self._constrain(x + mix)
                if sub.ffn == "none":
                    continue
                h2 = Lyr.rmsnorm(x, lp[f"{pre}.norm2"], cfg.norm_eps)
                if sub.ffn == "mlp":
                    f = Lyr.mlp_apply(lp, qp_body, cfg, h2,
                                      prefix=f"{pre}.mlp")
                else:
                    # serving semantics, like prefill: chunk tokens never
                    # compete for expert capacity (one-token decode can't
                    # overflow, so a dropping verify would diverge from
                    # the sequential decode it stands in for)
                    f = Lyr.moe_apply(lp, qp_body, cfg, h2,
                                      prefix=f"{pre}.moe",
                                      full_capacity=True, shapes=shp)
                x = self._constrain(x + f)
            return x, new_c

        bp = self._block_params(params)
        if self.n_blocks <= 2:
            new_list = []
            for i in range(self.n_blocks):
                x, nc = body(x, {"p": {k: v[i] for k, v in bp.items()},
                                 "c": {k: v[i] for k, v in caches.items()}})
                new_list.append(nc)
            new_caches = {k: jnp.stack([nc[k] for nc in new_list])
                          for k in new_list[0]}
        else:
            x, new_caches = jax.lax.scan(body, x, {"p": bp, "c": caches})
        if last_logit_only:
            x = x[:, -1:]
        x = Lyr.rmsnorm(self._constrain(x), params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)
        return logits, new_caches

    def prefill(self, params: dict, qparams: Optional[dict], caches: dict,
                tokens, vision_embeds=None, last_logit_only: bool = False):
        """One-shot parallel prefill: a single full-sequence pass that also
        fills the decode caches — K/V rows written at positions [0, S) in
        one slice update per layer, SSM/RWKV states left as they stand
        after the last prompt token. Numerically equivalent to S sequential
        `decode_step` calls but with GEMM-shaped (B, S) matmuls instead of
        S token-by-token dispatches (the engine's admission path).

        tokens: (B, S[, n_codebooks]). `caches` must be freshly initialized
        for these sequences (rows are overwritten from position 0) and,
        on windowed-attention configs, S must fit inside the window.
        Returns (logits (B, S, ...), caches); `last_logit_only` projects
        just the final position through the head (decode only feeds on
        that one — skips an (S-1) x vocab GEMM per admission)."""
        cfg = self.cfg
        params, qp_body = self._prequantize(params, qparams)
        x = self._embed_tokens(params, tokens)
        if cfg.vision_patches and vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        rope = Lyr.rope_tables(S, cfg.d_head, cfg.rope_theta)

        def body(x, inp):
            x = self._constrain(x)
            lp = inp["p"]
            cc = inp["c"]
            new_c = {}
            for sub, shp in zip(self.plan, self.shapes):
                pre = f"blocks.{sub.j}"
                h = Lyr.rmsnorm(x, lp[f"{pre}.norm1"], cfg.norm_eps)
                if sub.mixer == "attn":
                    mix, nc = Lyr.attn_apply(
                        lp, qp_body, cfg, h, rope=rope, window=cfg.window,
                        prefix=f"{pre}.attn", shapes=shp,
                        cache=(cc[f"{pre}.k"], cc[f"{pre}.v"],
                               jnp.zeros((), jnp.int32)))
                    new_c[f"{pre}.k"], new_c[f"{pre}.v"], _ = nc
                elif sub.mixer == "mamba":
                    mix, ns = Lyr.mamba_apply(lp, qp_body, cfg, h,
                                              prefix=f"{pre}.mamba",
                                              shapes=shp)
                    new_c[f"{pre}.h"], new_c[f"{pre}.conv"] = ns
                else:
                    mix, ns = Lyr.rwkv_timemix_apply(lp, qp_body, cfg, h,
                                                     prefix=f"{pre}.rwkv",
                                                     shapes=shp)
                    new_c[f"{pre}.tm_shift"], new_c[f"{pre}.wkv"] = ns
                x = self._constrain(x + mix)
                if sub.ffn == "none":
                    continue
                h2 = Lyr.rmsnorm(x, lp[f"{pre}.norm2"], cfg.norm_eps)
                if sub.ffn == "mlp":
                    f = Lyr.mlp_apply(lp, qp_body, cfg, h2, prefix=f"{pre}.mlp")
                elif sub.ffn == "moe":
                    # serving semantics: prompt tokens never compete for
                    # expert capacity (one-token decode can't overflow, so
                    # a dropping prefill would silently diverge from it)
                    f = Lyr.moe_apply(lp, qp_body, cfg, h2,
                                      prefix=f"{pre}.moe", full_capacity=True,
                                      shapes=shp)
                else:
                    f, ns = Lyr.rwkv_chanmix_apply(lp, qp_body, cfg, h2,
                                                   prefix=f"{pre}.rwkv")
                    new_c[f"{pre}.cm_shift"] = ns
                x = self._constrain(x + f)
            return x, new_c

        bp = self._block_params(params)
        if self.n_blocks <= 2:
            new_list = []
            for i in range(self.n_blocks):
                x, nc = body(x, {"p": {k: v[i] for k, v in bp.items()},
                                 "c": {k: v[i] for k, v in caches.items()}})
                new_list.append(nc)
            new_caches = {k: jnp.stack([nc[k] for nc in new_list])
                          for k in new_list[0]}
        else:
            x, new_caches = jax.lax.scan(body, x, {"p": bp, "c": caches})
        if last_logit_only:
            x = x[:, -1:]
        x = Lyr.rmsnorm(self._constrain(x), params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)
        if cfg.num_codebooks:
            B, St = logits.shape[:2]
            logits = logits.reshape(B, St, cfg.num_codebooks, cfg.vocab_padded)
        return logits, new_caches

    # -------------------------------------------------------------- graph
    def build_graph(self, act_quant: bool = False) -> GraphBuilder:
        """Trace graph + quant branches for QADG analysis.

        Stacked tensors: one vertex per (position-in-period, component);
        families over head groups / experts / channels apply uniformly
        across the n_blocks stack (per-stack granularity, DESIGN.md §2.2).
        """
        cfg = self.cfg
        gb = GraphBuilder()
        gb.input("in")
        gb.embedding("embed", "embed", out_dim=cfg.d_model,
                     non_prunable=True, after="in",
                     out_axis=(2 if cfg.num_codebooks else 1))
        resid = "embed"
        for sub in self.plan:
            pre = f"blocks.{sub.j}"
            gb.norm(f"{pre}.norm1", scale=f"{pre}.norm1", after=resid,
                    param_axis=1)
            mixer_v = self._graph_mixer(gb, sub, pre)
            resid = gb.add(f"{pre}.add1", [resid, mixer_v])
            if sub.ffn == "none":
                continue
            gb.norm(f"{pre}.norm2", scale=f"{pre}.norm2", after=resid,
                    param_axis=1)
            ffn_v = self._graph_ffn(gb, sub, pre, act_quant)
            resid = gb.add(f"{pre}.add2", [resid, ffn_v])
        gb.norm("final_norm", scale="final_norm", after=resid)
        tied = cfg.tie_embeddings and not cfg.num_codebooks
        head_param = "embed" if tied else "head"
        head_out = cfg.vocab_padded * max(cfg.num_codebooks, 1)
        gb.linear("head", head_param, out_dim=head_out,
                  non_prunable=True,
                  in_axis=(1 if tied else 0), out_axis=(0 if tied else 1),
                  after="final_norm")
        gb.attach_weight_quant("head", f"{head_param}.wq",
                               target_param=head_param)
        gb.output("out", after="head")
        return gb

    def _graph_mixer(self, gb: GraphBuilder, sub: SubLayer, pre: str) -> str:
        cfg = self.cfg
        if sub.mixer == "attn":
            gsz = cfg.gqa_group
            dh = cfg.d_head
            members = [(f"{pre}.attn.wq", 2, gsz * dh),
                       (f"{pre}.attn.wk", 2, dh),
                       (f"{pre}.attn.wv", 2, dh),
                       (f"{pre}.attn.wo", 1, gsz * dh)]
            if cfg.qkv_bias:
                members += [(f"{pre}.attn.bq", 1, gsz * dh),
                            (f"{pre}.attn.bk", 1, dh),
                            (f"{pre}.attn.bv", 1, dh)]
            spec = FamilySpec(name=f"{pre}.attn.kv_groups",
                              units=cfg.n_kv_heads, members=members,
                              kind="head_group")
            vid = gb.composite(
                f"{pre}.attn", "attention", spec,
                params={f"p{i}": m[0] for i, m in enumerate(members)},
                in_members=[(f"{pre}.attn.wq", 1), (f"{pre}.attn.wk", 1),
                            (f"{pre}.attn.wv", 1)],
                resid_members=[(f"{pre}.attn.wo", 2)],
                after=f"{pre}.norm1")
            for w in _QUANT_WEIGHTS["attn"]:
                gb.attach_weight_quant(vid, f"{pre}.attn.{w}.wq",
                                       target_param=f"{pre}.attn.{w}")
            return vid
        if sub.mixer == "mamba":
            Di = cfg.mamba.expand * cfg.d_model
            members = [(f"{pre}.mamba.in_proj_x", 2, 1),
                       (f"{pre}.mamba.in_proj_z", 2, 1),
                       (f"{pre}.mamba.conv_w", 2, 1),
                       (f"{pre}.mamba.x_proj", 1, 1),
                       (f"{pre}.mamba.dt_proj", 2, 1),
                       (f"{pre}.mamba.dt_bias", 1, 1),
                       (f"{pre}.mamba.A_log", 1, 1),
                       (f"{pre}.mamba.D", 1, 1),
                       (f"{pre}.mamba.out_proj", 1, 1)]
            spec = FamilySpec(name=f"{pre}.mamba.channels", units=Di,
                              members=members, kind="state")
            vid = gb.composite(
                f"{pre}.mamba", "mamba", spec,
                params={f"p{i}": m[0] for i, m in enumerate(members)},
                in_members=[(f"{pre}.mamba.in_proj_x", 1),
                            (f"{pre}.mamba.in_proj_z", 1)],
                resid_members=[(f"{pre}.mamba.out_proj", 2)],
                after=f"{pre}.norm1")
            for w in _QUANT_WEIGHTS["mamba"]:
                gb.attach_weight_quant(vid, f"{pre}.mamba.{w}.wq",
                                       target_param=f"{pre}.mamba.{w}")
            return vid
        # rwkv time-mix: heads are the removable unit
        dh = cfg.rwkv.head_size
        H = cfg.d_model // dh
        members = [(f"{pre}.rwkv.wr", 2, dh), (f"{pre}.rwkv.wk", 2, dh),
                   (f"{pre}.rwkv.wv", 2, dh), (f"{pre}.rwkv.wg", 2, dh),
                   (f"{pre}.rwkv.wo", 1, dh),
                   (f"{pre}.rwkv.decay_w2", 2, dh),
                   (f"{pre}.rwkv.decay_w0", 1, dh), (f"{pre}.rwkv.u", 1, dh),
                   (f"{pre}.rwkv.lnx_scale", 1, dh),
                   (f"{pre}.rwkv.lnx_bias", 1, dh)]
        spec = FamilySpec(name=f"{pre}.rwkv.heads", units=H, members=members,
                          kind="head_group")
        vid = gb.composite(
            f"{pre}.rwkv", "rwkv_timemix", spec,
            params={f"p{i}": m[0] for i, m in enumerate(members)},
            in_members=[(f"{pre}.rwkv.wr", 1), (f"{pre}.rwkv.wk", 1),
                        (f"{pre}.rwkv.wv", 1), (f"{pre}.rwkv.wg", 1),
                        (f"{pre}.rwkv.decay_w1", 1)],
            resid_members=[(f"{pre}.rwkv.wo", 2)],
            after=f"{pre}.norm1")
        for w in _QUANT_WEIGHTS["rwkv"]:
            gb.attach_weight_quant(vid, f"{pre}.rwkv.{w}.wq",
                                   target_param=f"{pre}.rwkv.{w}")
        return vid

    def _graph_ffn(self, gb: GraphBuilder, sub: SubLayer, pre: str,
                   act_quant: bool) -> str:
        cfg = self.cfg
        if sub.ffn == "mlp":
            # gate/up produce the hidden space (tied via the product),
            # down consumes it — expressed with generic vertices so the
            # dependency analysis (and inserted act-quant branches) apply.
            g = gb.linear(f"{pre}.mlp.gate", f"{pre}.mlp.w_gate",
                          out_dim=cfg.d_ff, in_axis=1, out_axis=2,
                          after=f"{pre}.norm2")
            u = gb.linear(f"{pre}.mlp.up", f"{pre}.mlp.w_up",
                          out_dim=cfg.d_ff, in_axis=1, out_axis=2,
                          after=f"{pre}.norm2")
            m = gb.add(f"{pre}.mlp.prod", [g, u])
            a = gb.act(f"{pre}.mlp.silu", after=m)
            dn = gb.linear(f"{pre}.mlp.down", f"{pre}.mlp.w_down",
                           in_axis=1, out_axis=2, out_dim=cfg.d_model,
                           non_prunable=True, after=a)
            for w in ("gate", "up", "down"):
                gb.attach_weight_quant(f"{pre}.mlp.{w}",
                                       f"{pre}.mlp.w_{w}.wq")
            if act_quant:
                gb.insert_act_quant(a, dn, f"{pre}.mlp.mlp_act.aq")
            return dn
        if sub.ffn == "moe":
            E = cfg.moe.n_experts
            members = [(f"{pre}.moe.router", 2, 1),
                       (f"{pre}.moe.we_gate", 1, 1),
                       (f"{pre}.moe.we_up", 1, 1),
                       (f"{pre}.moe.we_down", 1, 1)]
            spec = FamilySpec(name=f"{pre}.moe.experts", units=E,
                              members=members, kind="expert")
            in_m = [(f"{pre}.moe.router", 1), (f"{pre}.moe.we_gate", 2),
                    (f"{pre}.moe.we_up", 2)]
            res_m = [(f"{pre}.moe.we_down", 3)]
            if cfg.moe.shared_expert:
                in_m += [(f"{pre}.moe.shared.w_gate", 1),
                         (f"{pre}.moe.shared.w_up", 1)]
                res_m += [(f"{pre}.moe.shared.w_down", 2)]
            vid = gb.composite(
                f"{pre}.moe", "moe", spec,
                params={f"p{i}": m[0] for i, m in enumerate(members)},
                in_members=in_m, resid_members=res_m, after=f"{pre}.norm2")
            for w in _QUANT_WEIGHTS["moe"]:
                gb.attach_weight_quant(vid, f"{pre}.moe.{w}.wq",
                                       target_param=f"{pre}.moe.{w}")
            return vid
        # rwkv channel-mix: hidden channels family
        members = [(f"{pre}.rwkv.cm_k", 2, 1), (f"{pre}.rwkv.cm_v", 1, 1)]
        spec = FamilySpec(name=f"{pre}.rwkv.cm_hidden", units=cfg.d_ff,
                          members=members, kind="channel")
        vid = gb.composite(
            f"{pre}.rwkv.cm", "rwkv_chanmix", spec,
            params={f"p{i}": m[0] for i, m in enumerate(members)},
            in_members=[(f"{pre}.rwkv.cm_k", 1), (f"{pre}.rwkv.cm_r", 1)],
            resid_members=[(f"{pre}.rwkv.cm_v", 2), (f"{pre}.rwkv.cm_r", 2)],
            after=f"{pre}.norm2")
        for w in _QUANT_WEIGHTS["chanmix"]:
            gb.attach_weight_quant(vid, f"{pre}.rwkv.{w}.wq",
                                   target_param=f"{pre}.rwkv.{w}")
        return vid
