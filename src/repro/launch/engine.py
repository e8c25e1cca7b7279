"""Continuous-batching serving engine over the compressed GEMM path.

The static `serve_loop` (launch/serve.py) decodes one fixed batch in
lockstep: every sequence shares a scalar position, prefill is a sequential
per-token loop, and a finished sequence keeps burning decode slots until
the longest one ends. This engine replaces all three:

- **request queue + admission/eviction** — requests arrive with their own
  prompt and token budget; a finished request frees its slot immediately
  and the next queued request is admitted into it.
- **slot-based (paged-lite) KV management** — the caches are one
  `LM.init_cache(max_slots, max_seq)` arena; each slot is a cache row
  owned by at most one request. Admission overwrites the *whole* row (the
  prefill builds it in a fresh zeroed cache, insertion is a single
  `dynamic_update_slice` per leaf), so no stale state survives eviction.
- **per-slot positions** — `LM.decode_step` takes a (B,) position vector,
  so slots at different progress share one batched decode dispatch (each
  row ropes at its own absolute position and masks its own cache length).
- **one-shot prefill** — `LM.prefill` fills a cache row with a single
  full-sequence forward (GEMM-shaped (1, S) matmuls) instead of S
  sequential decode steps.

Three jitted functions run everything: `_prefill` (one per distinct
prompt length), `_insert` (slot index is a traced scalar — one compile
serves every slot), and `_decode` (one compile, period). Works unchanged
on dense fake-quant params and on `--compressed` Subnet int codes —
`core.subnet.prepare_serving` resolves the pair once and every jit closes
over the same arrays.

Two orthogonal scaling axes ride on top (PR 9, DESIGN.md §4.12):

- **tensor parallelism** — `Engine(..., mesh=make_tp_mesh(n))` shards the
  served params (attention heads / MLP hidden / vocab through the
  training `ShardingPlan` rules, int codes and packed word streams by
  name mapping) and the KV arena — contiguous *and* paged pools — by KV
  head over the mesh's `model` axis. Every jit pins its output shardings
  so the arena stays device-resident and sharded across the whole decode
  loop; page tables and slot bookkeeping stay host-side, unchanged. An
  N-device engine is token-identical to the 1-device engine (the
  `serve --tp --smoke` parity matrix pins dense/pruned/packed/paged).
- **disaggregated chunked prefill** — `scheduler=
  ChunkedPrefillScheduler(chunk)` (launch/scheduler.py) splits each
  prompt's prefill into bounded chunks staged into a private row cache
  (`LM.verify_chunk` at absolute positions), interleaving one decode
  batch per chunk so a long prompt can no longer head-of-line-block the
  active slots; the finished row hands off to a free slot through the
  engine's handoff queue exactly like a one-shot prefill row would.

Smoke:
  PYTHONPATH=src python -m repro.launch.serve --smoke --compressed \
      --prompt-lens 12,5 --gen 8
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.quant import kv_quant_decode, kv_quant_encode
from repro.core.subnet import (compression_report, prepare_serving,
                               tree_bytes)
from repro.data.synthetic import batch_for
from repro.kernels import dispatch
from repro.launch import paging
from repro.models import layers as model_layers
from repro.models.transformer import LM


def _kv_split(caches: dict) -> tuple[list[str], list[str]]:
    """Partition cache keys into attention K/V leaves (pool pages under
    the paged arena) and recurrent-state leaves (always per-slot)."""
    kv = sorted(k for k in caches
                if k.endswith(".k") or k.endswith(".v"))
    state = sorted(k for k in caches
                   if k not in kv and not k.endswith("_scale"))
    return kv, state


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    submit_t: float = 0.0
    admit_t: float = 0.0
    finish_t: float = 0.0

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens


class Engine:
    """Continuous-batching decode over a slot arena.

    Drive it either one `step()` at a time (admission + one batched decode
    dispatch) or with `run()` until every submitted request finished.
    """

    def __init__(self, lm: LM, params: dict, qparams: Optional[dict], *,
                 max_slots: int = 4, max_seq: int = 64,
                 draft=None, draft_k: int = 4, paged: bool = False,
                 page_size: int = 16, kv_bits: Optional[int] = None,
                 n_pages: Optional[int] = None, prefix_sharing: bool = True,
                 mesh=None, param_axes: Optional[dict] = None,
                 scheduler=None):
        cfg = lm.cfg
        if cfg.num_codebooks or cfg.vision_patches:
            raise ValueError("the engine serves plain token LMs; codebook "
                             "and VLM prompts need a modality frontend — "
                             "use the static loop (serve.py --static / "
                             "serve_loop) for these archs")
        self.lm = lm
        self.max_slots = max_slots
        self.max_seq = max_seq
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self._cache_dtype = dt

        # --- tensor parallelism (DESIGN.md §4.12) ----------------------
        # params shard through the training ShardingPlan's TP rules (the
        # served dict's derived keys — .codes / .packed{b} / .scale — map
        # back to their base weight's axes by name); the KV arena shards
        # by KV head. Shapes the mesh can't divide replicate, recorded in
        # `tp_fallbacks` so the smoke can report them.
        self.mesh = mesh
        self._rep = None            # NamedSharding(mesh, P()) when TP
        self._arena_sh = None       # per-leaf shardings: slot/page arena
        self._row_sh = None         # ... a (1, max_seq) staging row
        self._darena_sh = None      # ... draft arena / draft row
        self._drow_sh = None
        self.tp_fallbacks: list = []
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec
            from repro.distributed import sharding as shlib
            if param_axes is None:
                # recover the logical axes without materializing a second
                # init: abstract-eval lm.init and capture the axes dict it
                # returns (names are stable across pruning — apply_slim_plan
                # reshapes, it never renames)
                captured: dict = {}

                def _cap(key):
                    p, a = lm.init(key)
                    captured.update(a)
                    return p

                jax.eval_shape(_cap, jax.random.PRNGKey(0))
                param_axes = captured
            plan = shlib.make_plan(mesh, mode="tp")
            pspecs = shlib.serving_param_specs(plan, param_axes, params)
            params = jax.device_put(
                params, {k: NamedSharding(mesh, s)
                         for k, s in pspecs.items()})
            if qparams is not None:
                qparams = jax.device_put(qparams,
                                         NamedSharding(mesh, PSpec()))
            self._rep = NamedSharding(mesh, PSpec())
            self.tp_fallbacks = list(plan.fallbacks)
        self.param_axes = dict(param_axes or {})
        self.params = params
        self.qparams = qparams
        mesh_ = mesh

        def _jit(fn, static_argnums=(), out_shardings=None):
            # every engine jit pins its output shardings under TP so the
            # arena never silently de-shards between dispatches, and traces
            # under the mesh's kernel backend (`dispatch.backend_for_mesh`)
            # with the residual stream pinned replicated (`LM._constrain`:
            # norms then reduce over whole rows, as on one device); without
            # a mesh this is exactly jax.jit
            if mesh_ is None:
                return jax.jit(fn, static_argnums=static_argnums)
            lms = [lm] + ([draft.lm] if draft is not None else [])

            @functools.wraps(fn)
            def on_mesh(*args):
                saved = [m.act_sharding for m in lms]
                for m in lms:
                    m.act_sharding = self._rep
                try:
                    with dispatch.on_mesh(mesh_):
                        return fn(*args)
                finally:
                    for m, a in zip(lms, saved):
                        m.act_sharding = a

            if out_shardings is None:
                return jax.jit(on_mesh, static_argnums=static_argnums)
            return jax.jit(on_mesh, static_argnums=static_argnums,
                           out_shardings=out_shardings)
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.kv_bits = kv_bits
        if kv_bits is not None and not self.paged:
            raise ValueError("kv_bits quantizes the *paged* page store; "
                             "pass paged=True")
        if self.paged:
            # paged block arena: attention K/V live in shared page pools
            # addressed through per-slot page tables; admission/eviction
            # become host-side allocator ops (launch/paging.py)
            self.Lp = paging.pages_for_rows(max_seq, self.page_size)
            if n_pages is None:
                # every slot can hold a full-length request, plus one
                # table's worth of headroom for prefix-cache entries
                n_pages = paging.N_RESERVED + (max_slots + 1) * self.Lp
            self.n_pages = int(n_pages)
            self.alloc = paging.PageAllocator(self.n_pages, self.page_size)
            self.prefix_cache = (paging.PrefixCache(self.alloc)
                                 if prefix_sharing else None)
            self.page_table = np.full((max_slots, self.Lp),
                                      paging.TRASH_PAGE, np.int32)
            self.slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
            self.caches = lm.init_paged_cache(max_slots, self.n_pages,
                                              self.page_size, dtype=dt,
                                              kv_bits=kv_bits)
        else:
            self.caches = lm.init_cache(max_slots, max_seq, dtype=dt)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.distributed import sharding as shlib
            arena_specs = shlib.kv_cache_specs(
                mesh, {k: v.shape for k, v in self.caches.items()})
            self._arena_sh = {k: NamedSharding(mesh, s)
                              for k, s in arena_specs.items()}
            self.caches = jax.device_put(self.caches, self._arena_sh)
            # prefill staging rows are contiguous (1, max_seq) caches even
            # under the paged arena — they get their own spec set
            row_tmpl = jax.eval_shape(
                lambda: lm.init_cache(1, max_seq, dtype=dt))
            row_specs = shlib.kv_cache_specs(
                mesh, {k: v.shape for k, v in row_tmpl.items()})
            self._row_sh = {k: NamedSharding(mesh, s)
                            for k, s in row_specs.items()}
        # host-side slot table: position, last emitted token, owner
        self.pos = np.zeros((max_slots,), np.int32)
        self.last_tok = np.zeros((max_slots,), np.int32)
        self.active: list[Optional[Request]] = [None] * max_slots
        self.queue: deque[Request] = deque()
        self.done: dict[int, Request] = {}
        self._next_rid = 0
        self.stats = {"decode_steps": 0, "decode_tokens": 0, "decode_s": 0.0,
                      "prefills": 0, "prefill_tokens": 0, "prefill_s": 0.0,
                      "draft_prefills": 0, "draft_prefill_tokens": 0,
                      "draft_prefill_s": 0.0, "prefix_hits": 0,
                      "admitted": 0, "evicted": 0,
                      "spec_steps": 0, "spec_drafted": 0, "spec_accepted": 0,
                      "prefill_chunks": 0, "chunked_prefills": 0,
                      "decode_steps_mid_prefill": 0}
        self.serving_meta: dict = {}   # prepare_serving meta (build_engine)

        # speculative decoding: a DraftModel (launch/speculative.py) adds
        # a second KV arena sharing this engine's slot/position tables
        self.draft = draft
        self.draft_k = int(draft_k)
        self.dcaches = None
        if draft is not None:
            from repro.launch.speculative import make_spec_step
            if cfg.window > 0:
                raise ValueError(
                    "speculative decoding needs full (window == 0) KV "
                    "arenas: a ring wrap overwrites pre-wrap rows that a "
                    "rejection could never roll back")
            bad = sorted({s.mixer for s in lm.plan if s.mixer != "attn"})
            if bad:
                raise ValueError(
                    f"speculative decoding needs attention mixers "
                    f"everywhere (rollback zeroes KV rows); plan has "
                    f"{bad} layers whose recurrent state cannot be "
                    f"rolled back")
            if not 1 <= self.draft_k < max_seq:
                raise ValueError(
                    f"draft_k={self.draft_k} must be in [1, "
                    f"max_seq={max_seq})")
            if self.paged:
                # the draft arena pages in lockstep: its own pools (at the
                # draft's sliced KV shapes) indexed by the *same* page
                # table and allocator — one allocation covers both arenas
                self.dcaches = draft.lm.init_paged_cache(
                    max_slots, self.n_pages, self.page_size, dtype=dt,
                    kv_bits=kv_bits)
            else:
                self.dcaches = draft.lm.init_cache(max_slots, max_seq,
                                                   dtype=dt)
            if mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as PSpec
                from repro.distributed import sharding as shlib
                # the draft arena shards by *its* (sliced) KV heads; the
                # draft's served params shard through the same TP rules
                dspecs = shlib.kv_cache_specs(
                    mesh, {k: v.shape for k, v in self.dcaches.items()})
                self._darena_sh = {k: NamedSharding(mesh, s)
                                   for k, s in dspecs.items()}
                self.dcaches = jax.device_put(self.dcaches, self._darena_sh)
                drow_tmpl = jax.eval_shape(
                    lambda: draft.lm.init_cache(1, max_seq, dtype=dt))
                drow_specs = shlib.kv_cache_specs(
                    mesh, {k: v.shape for k, v in drow_tmpl.items()})
                self._drow_sh = {k: NamedSharding(mesh, s)
                                 for k, s in drow_specs.items()}
                dcap: dict = {}

                def _dcap(key):
                    p, a = draft.lm.init(key)
                    dcap.update(a)
                    return p

                jax.eval_shape(_dcap, jax.random.PRNGKey(0))
                dplan = shlib.make_plan(mesh, mode="tp")
                dpspecs = shlib.serving_param_specs(dplan, dcap,
                                                    draft.params)
                draft.params = jax.device_put(
                    draft.params, {k: NamedSharding(mesh, s)
                                   for k, s in dpspecs.items()})
                if draft.qparams is not None:
                    draft.qparams = jax.device_put(
                        draft.qparams, NamedSharding(mesh, PSpec()))
                self.tp_fallbacks += [("draft:" + n, a, d)
                                      for n, a, d in dplan.fallbacks]
            spec_fn = make_spec_step(lm, draft.lm)
            self._spec = _jit(
                spec_fn, static_argnums=(8,),
                out_shardings=(self._rep, self._rep, self._arena_sh,
                               self._darena_sh))

            def _prefill_draft(dparams, dqparams, tokens):
                c = draft.lm.init_cache(1, max_seq, dtype=dt)
                _, c = draft.lm.prefill(dparams, dqparams, c, tokens,
                                        last_logit_only=True)
                return c

            self._prefill_draft = _jit(_prefill_draft,
                                       out_shardings=self._drow_sh)

        def _prefill(params, qparams, tokens):
            caches = lm.init_cache(1, max_seq, dtype=dt)
            # only the last position feeds decode: skip the (S-1) x vocab
            # head GEMM the full-logits prefill would burn per admission
            logits, caches = lm.prefill(params, qparams, caches, tokens,
                                        last_logit_only=True)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, caches

        def _insert(caches, row, slot):
            def ins(c, r):
                idx = (0, slot) + (0,) * (c.ndim - 2)
                return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), idx)
            return jax.tree_util.tree_map(ins, caches, row)

        def _decode(params, qparams, caches, tok, pos):
            logits, caches = lm.decode_step(params, qparams, caches, tok, pos)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, caches

        def _decode_window(params, qparams, caches, tok, pos, k):
            # k event-free steps fused into one dispatch: between two
            # admission/eviction events (whose timing is count-based and
            # known in advance) nothing on the host needs the tokens, so
            # the loop runs on-device and syncs once per window.
            def body(carry, _):
                caches, tok, pos = carry
                logits, caches = lm.decode_step(params, qparams, caches,
                                                tok, pos)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (caches, nxt[:, None], pos + 1), nxt

            (caches, _, _), toks = jax.lax.scan(
                body, (caches, tok, pos), None, length=k)
            return toks, caches     # toks: (k, B)

        self._prefill = _jit(_prefill,
                             out_shardings=((self._rep, self._row_sh)
                                            if mesh is not None else None))
        # _insert used to serve both arenas (target AND draft rows through
        # one unpinned jit, keyed by avals) relying on dynamic_update_slice
        # propagating the operand's sharding — the exact operand-propagation
        # hole the sharding-pin audit (repro.analysis, DESIGN.md §4.13)
        # exists to flag. Each arena now gets its own pinned clone:
        # _insert writes target rows under the target arena's sharding
        # tree, _insert_d (created only with a draft attached) under the
        # draft's. Same function, same per-arena compile count as before.
        self._insert = _jit(_insert, out_shardings=self._arena_sh)
        self._insert_d = (_jit(_insert, out_shardings=self._darena_sh)
                          if draft is not None else None)
        self._decode = _jit(_decode,
                            out_shardings=((self._rep, self._arena_sh)
                                           if mesh is not None else None))
        # one compile per distinct window length (static scan trip count)
        self._decode_window = _jit(
            _decode_window, static_argnums=(5,),
            out_shardings=((self._rep, self._arena_sh)
                           if mesh is not None else None))

        if self.paged:
            P = self.page_size
            Lp = self.Lp
            kvb = self.kv_bits
            kv_keys, state_keys = _kv_split(self.caches)

            def _pages_view(pt):
                return model_layers.PagedView(table=pt, page_size=P,
                                              seq_len=max_seq, kv_bits=kvb)

            def make_insert_pages(kv, state, out_sh=None):
                # scatter a fresh (1, max_seq) prefill cache into the
                # slot's first npp physical pages (whole-page writes: the
                # prefill's zero tail keeps page remainders zero), and
                # slot-insert the recurrent-state leaves as before
                def ins(caches, row, slot, phys, npp):
                    new = dict(caches)
                    for kk in kv:
                        r = row[kk][:, 0]                 # (nb, S, KVh, dh)
                        pad = npp * P - r.shape[1]
                        if pad > 0:
                            r = jnp.pad(r, ((0, 0), (0, pad))
                                        + ((0, 0),) * (r.ndim - 2))
                        blocks = r[:, :npp * P].reshape(
                            (r.shape[0], npp, P) + r.shape[2:])
                        if kvb is not None:
                            codes, scale = kv_quant_encode(blocks, kvb)
                            new[kk] = caches[kk].at[:, phys].set(
                                codes.astype(caches[kk].dtype))
                            sk = kk + "_scale"
                            new[sk] = caches[sk].at[:, phys].set(scale)
                        else:
                            new[kk] = caches[kk].at[:, phys].set(
                                blocks.astype(caches[kk].dtype))
                    for sk in state:
                        c = caches[sk]
                        idx = (0, slot) + (0,) * (c.ndim - 2)
                        new[sk] = jax.lax.dynamic_update_slice(
                            c, row[sk].astype(c.dtype), idx)
                    return new
                return _jit(ins, static_argnums=(4,), out_shardings=out_sh)

            def make_zero_pages(kv, out_sh=None):
                def zero(caches, ids):
                    new = dict(caches)
                    for kk in kv:
                        new[kk] = caches[kk].at[:, ids].set(
                            jnp.zeros((), caches[kk].dtype))
                        if kvb is not None:
                            sk = kk + "_scale"
                            new[sk] = caches[sk].at[:, ids].set(
                                jnp.zeros((), caches[sk].dtype))
                    return new
                return _jit(zero, out_shardings=out_sh)

            def make_copy_page(kv, out_sh=None):
                def cp(caches, src, dst):
                    new = dict(caches)
                    for kk in kv:
                        new[kk] = caches[kk].at[:, dst].set(caches[kk][:, src])
                        if kvb is not None:
                            sk = kk + "_scale"
                            new[sk] = caches[sk].at[:, dst].set(
                                caches[sk][:, src])
                    return new
                return _jit(cp, out_shardings=out_sh)

            def _decode_paged(params, qparams, caches, tok, pos, pt):
                logits, caches = lm.decode_step(params, qparams, caches, tok,
                                                pos, pages=_pages_view(pt))
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt, caches

            def _decode_window_paged(params, qparams, caches, tok, pos, pt,
                                     k):
                pages = _pages_view(pt)

                def body(carry, _):
                    caches, tok, pos = carry
                    logits, caches = lm.decode_step(params, qparams, caches,
                                                    tok, pos, pages=pages)
                    nxt = jnp.argmax(logits[:, -1],
                                     axis=-1).astype(jnp.int32)
                    return (caches, nxt[:, None], pos + 1), nxt

                (caches, _, _), toks = jax.lax.scan(
                    body, (caches, tok, pos), None, length=k)
                return toks, caches

            self._insert_pages = make_insert_pages(kv_keys, state_keys,
                                                   self._arena_sh)
            self._zero_pages = make_zero_pages(kv_keys, self._arena_sh)
            self._copy_page = make_copy_page(kv_keys, self._arena_sh)
            self._decode_paged = _jit(
                _decode_paged,
                out_shardings=((self._rep, self._arena_sh)
                               if mesh is not None else None))
            self._decode_window_paged = _jit(
                _decode_window_paged, static_argnums=(6,),
                out_shardings=((self._rep, self._arena_sh)
                               if mesh is not None else None))

            if draft is not None:
                dkv_keys, dstate_keys = _kv_split(self.dcaches)
                self._insert_pages_d = make_insert_pages(dkv_keys,
                                                         dstate_keys,
                                                         self._darena_sh)
                self._zero_pages_d = make_zero_pages(dkv_keys,
                                                     self._darena_sh)
                self._copy_page_d = make_copy_page(dkv_keys,
                                                   self._darena_sh)

                def make_gather(kv, state):
                    # materialize each slot's contiguous (max_seq-row)
                    # arena view from its pages: gather, dequantize if the
                    # pool holds codes, and SLICE to the logical length —
                    # the slice keeps the spec step's reductions the exact
                    # shape the contiguous engine runs, so token identity
                    # survives the round trip
                    def gather(caches, pt):
                        views = {}
                        for kk in kv:
                            pages_ = jnp.take(caches[kk], pt, axis=1)
                            if kvb is not None:
                                sc = jnp.take(caches[kk + "_scale"], pt,
                                              axis=1)
                                pages_ = kv_quant_decode(pages_, sc, kvb)
                            rows = pages_.reshape(
                                pages_.shape[:2] + (Lp * P,)
                                + pages_.shape[4:])
                            views[kk] = rows[:, :, :max_seq].astype(dt)
                        for sk in state:
                            views[sk] = caches[sk]
                        return views
                    return gather

                def make_scatter(kv):
                    # write back only the pages a spec round could have
                    # touched: rows [pos, pos+k] span at most k//P + 2
                    # logical pages per slot. Clamped duplicates write
                    # identical blocks; pages past a slot's allocation
                    # alias the zero page and receive (exactly) zeros.
                    def scatter(caches, views, pt, pos, k):
                        new = dict(caches)
                        first_lp = pos // P
                        npg = min(k // P + 2, Lp)
                        for kk in kv:
                            view = views[kk]      # (nb, B, max_seq, ...)
                            pad = Lp * P - view.shape[2]
                            vp = jnp.pad(view, ((0, 0), (0, 0), (0, pad))
                                         + ((0, 0),) * (view.ndim - 3))
                            vB = jnp.moveaxis(vp, 1, 0)   # (B, nb, rows, .)
                            for j in range(npg):
                                lp = jnp.clip(first_lp + j, 0, Lp - 1)
                                phys = jnp.take_along_axis(
                                    pt, lp[:, None], axis=1)[:, 0]
                                blk = jax.vmap(
                                    lambda vb, s: jax.lax.dynamic_slice_in_dim(
                                        vb, s, P, axis=1))(vB, lp * P)
                                blk = jnp.moveaxis(blk, 0, 1)
                                if kvb is not None:
                                    codes, scale = kv_quant_encode(blk, kvb)
                                    new[kk] = new[kk].at[:, phys].set(
                                        codes.astype(new[kk].dtype))
                                    sk = kk + "_scale"
                                    new[sk] = new[sk].at[:, phys].set(scale)
                                else:
                                    new[kk] = new[kk].at[:, phys].set(
                                        blk.astype(new[kk].dtype))
                        return new
                    return scatter

                tgather = make_gather(kv_keys, state_keys)
                dgather = make_gather(dkv_keys, dstate_keys)
                tscatter = make_scatter(kv_keys)
                dscatter = make_scatter(dkv_keys)

                def _spec_paged(tp, tq, dp, dq, tc, dc, tok, pos, pt, k):
                    tv = tgather(tc, pt)
                    dv = dgather(dc, pt)
                    tgt, ncm, tv, dv = spec_fn(tp, tq, dp, dq, tv, dv,
                                               tok, pos, k)
                    tc = tscatter(tc, tv, pt, pos, k)
                    dc = dscatter(dc, dv, pt, pos, k)
                    return tgt, ncm, tc, dc

                self._spec_paged = _jit(
                    _spec_paged, static_argnums=(9,),
                    out_shardings=(self._rep, self._rep, self._arena_sh,
                                   self._darena_sh))

        # --- step scheduling policy + chunked-prefill staging ----------
        from repro.launch.scheduler import OneShotScheduler
        self.scheduler = scheduler if scheduler is not None \
            else OneShotScheduler()
        self._handoff: deque = deque()     # (req, first_token, row) staged
        self._prefill_job = None           # scheduler.PrefillJob in flight
        chunk = getattr(self.scheduler, "chunk", None)
        self._chunk = int(chunk) if chunk else None

        def _fresh_row():
            row = lm.init_cache(1, max_seq, dtype=dt)
            if mesh_ is not None:
                row = jax.device_put(row, self._row_sh)
            return row

        self._fresh_row = _fresh_row
        if self._chunk:
            # chunked prefill stages through LM.verify_chunk (absolute
            # positions into an existing cache), which carries the same
            # preconditions as speculative rollback
            if cfg.window > 0:
                raise ValueError(
                    "chunked prefill needs full (window == 0) KV arenas: "
                    "verify_chunk writes at absolute positions and a ring "
                    "wrap would fold chunk rows onto each other")
            bad = sorted({s.mixer for s in lm.plan if s.mixer != "attn"})
            if bad:
                raise ValueError(
                    f"chunked prefill needs attention mixers everywhere "
                    f"(each chunk resumes from cache rows alone); plan "
                    f"has {bad} layers with recurrent state that one-shot "
                    f"prefill threads internally")

            def _prefill_chunk(params, qparams, caches, tokens, pos):
                # verify_chunk semantics: tokens[:, 0] is the first
                # uncommitted prompt row, K/V land at rows
                # [pos, pos+T), and logits[:, -1] predicts the token
                # after the last fed row — on the final chunk that IS the
                # request's first generated token, same as _prefill's
                logits, caches = lm.verify_chunk(params, qparams, caches,
                                                 tokens, pos,
                                                 last_logit_only=True)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt, caches

            self._prefill_chunk = _jit(
                _prefill_chunk,
                out_shardings=((self._rep, self._row_sh)
                               if mesh_ is not None else None))

    # ------------------------------------------------------------- requests
    def submit(self, prompt, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        # rows actually written: the prompt occupies [0, S), the first
        # token comes out of the prefill itself, and the last of the
        # N-1 decode steps writes row S+N-2 — so a request needs S+N-1
        # arena rows (checking S+N left one row per slot unusable)
        if prompt.size + max_new_tokens - 1 > self.max_seq:
            raise ValueError(
                f"request needs {prompt.size + max_new_tokens - 1} cache "
                f"rows, arena rows hold {self.max_seq}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.paged:
            need = paging.pages_for_rows(
                prompt.size + max_new_tokens - 1, self.page_size)
            if need > self.n_pages - paging.N_RESERVED:
                raise ValueError(
                    f"request needs {need} KV pages, pool holds "
                    f"{self.n_pages - paging.N_RESERVED} allocatable pages")
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=max_new_tokens,
                                  submit_t=time.time()))
        return rid

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.active)

    @property
    def pending(self) -> bool:
        return (bool(self.queue) or self.n_active > 0
                or self._prefill_job is not None or bool(self._handoff))

    # ------------------------------------------------------------ lifecycle
    def _admit(self) -> int:
        """Prefill queued requests into free slots. Returns #admitted."""
        admitted = 0
        if self.paged:
            self._flush_dirty()
        blocked = False
        for slot in range(self.max_slots):
            if blocked:
                break
            # retry the same slot until a request actually occupies it:
            # a one-token request completes at admission and must not
            # leave the slot empty while the queue still has work
            while self.active[slot] is None and self.queue:
                req = self.queue.popleft()
                if self.paged:
                    got = self._admit_paged(req, slot)
                    if got is None:
                        # allocator pressure even after dropping prefix
                        # entries: requeue and wait for an eviction
                        self.queue.appendleft(req)
                        blocked = True
                        break
                    admitted += int(got)
                    continue
                t0 = time.time()
                nxt, row = self._prefill(self.params, self.qparams,
                                         jnp.asarray(req.prompt)[None])
                first = int(jax.block_until_ready(nxt)[0])
                self.stats["prefill_s"] += time.time() - t0
                self.stats["prefills"] += 1
                self.stats["prefill_tokens"] += int(req.prompt.size)
                self.stats["admitted"] += 1
                req.admit_t = time.time()
                req.tokens.append(first)
                if req.done:    # one-token request: never occupies a slot
                    self._finish(req)
                    continue
                self.caches = self._insert(self.caches, row, jnp.int32(slot))
                if self.draft is not None:
                    # the draft arena admits in lockstep: its own one-shot
                    # prefill (at the draft's sliced shapes) into the same
                    # slot, so both arenas agree on position bookkeeping
                    # from the first speculative round. Its wall time and
                    # tokens are draft work — they ride their own
                    # counters, not the target prefill rate's
                    t1 = time.time()
                    drow = self._prefill_draft(self.draft.params,
                                               self.draft.qparams,
                                               jnp.asarray(req.prompt)[None])
                    self.dcaches = self._insert_d(self.dcaches, drow,
                                                  jnp.int32(slot))
                    jax.block_until_ready(
                        jax.tree_util.tree_leaves(self.dcaches)[0])
                    self.stats["draft_prefill_s"] += time.time() - t1
                    self.stats["draft_prefills"] += 1
                    self.stats["draft_prefill_tokens"] += int(req.prompt.size)
                self.pos[slot] = req.prompt.size
                self.last_tok[slot] = first
                req.slot = slot
                self.active[slot] = req
                admitted += 1
        return admitted

    # ------------------------------------------------------ paged lifecycle
    def _flush_dirty(self) -> None:
        """Zero released pages on device and return them to the free list
        (the allocator's zero-before-reuse contract). Batched into pow2
        buckets so the compiled-shape set stays bounded; the padding ids
        hit the reserved zero page, where writing zeros is a no-op."""
        dirty = self.alloc.take_dirty()
        if not dirty:
            return
        m = 1
        while m < len(dirty):
            m *= 2
        ids = np.full((m,), paging.ZERO_PAGE, np.int32)
        ids[:len(dirty)] = dirty
        ids = jnp.asarray(ids)
        self.caches = self._zero_pages(self.caches, ids)
        if self.dcaches is not None:
            self.dcaches = self._zero_pages_d(self.dcaches, ids)
        self.alloc.mark_zeroed(dirty)

    def _reserve_pages(self, n: int, keep_last: bool = False) -> bool:
        """Make n pages allocatable, dropping LRU prefix-cache entries
        under pressure. `keep_last` protects the most-recently-used entry
        (the hit being admitted against)."""
        floor = 1 if keep_last else 0
        while not self.alloc.can_alloc(n):
            if self.prefix_cache is None or len(self.prefix_cache) <= floor:
                return False
            self.prefix_cache.drop_lru()
            self._flush_dirty()
        return True

    def _admit_paged(self, req: Request, slot: int,
                     prefilled=None) -> Optional[bool]:
        """Admit one request into `slot` under the paged arena. Returns
        True (occupies the slot), False (finished at admission — retry
        the slot), or None (allocator pressure — requeue).

        `prefilled=(first_token, row_cache)` supplies an already-staged
        chunked prefill (the handoff path): the prefill dispatch and its
        stats are skipped, everything downstream — page scatter, draft
        prefill, prefix-cache registration — runs identically."""
        P = self.page_size
        S = int(req.prompt.size)
        npg_req = paging.pages_for_rows(S + req.max_new_tokens - 1, P)
        n_full = S // P              # pages fully covered by prompt rows
        partial = S % P != 0
        cache = self.prefix_cache
        ent = cache.lookup(req.prompt) if cache is not None else None

        if req.max_new_tokens == 1:
            # one-token request: the answer is the (possibly memoized)
            # prefill argmax — no pages, no slot
            if ent is not None:
                first = int(ent.first_token)
                self.stats["prefix_hits"] += 1
            elif prefilled is not None:
                first = int(prefilled[0])
            else:
                t0 = time.time()
                nxt, _ = self._prefill(self.params, self.qparams,
                                       jnp.asarray(req.prompt)[None])
                first = int(jax.block_until_ready(nxt)[0])
                self.stats["prefill_s"] += time.time() - t0
                self.stats["prefills"] += 1
                self.stats["prefill_tokens"] += S
            self.stats["admitted"] += 1
            req.admit_t = time.time()
            req.tokens.append(first)
            self._finish(req)
            return False

        if ent is not None:
            # prefix hit: share the full prompt pages in place (one more
            # refcount), CoW-copy the pristine tail template into an
            # owned page, reuse the memoized first token — and skip both
            # prefill dispatches entirely
            n_owned = npg_req - n_full
            if not self._reserve_pages(n_owned, keep_last=True):
                return None
            owned = self.alloc.alloc(n_owned)
            self.alloc.retain(ent.full_pages)
            pages = list(ent.full_pages) + owned
            if partial:
                src = jnp.int32(ent.tail_page)
                dst = jnp.int32(owned[0])
                self.caches = self._copy_page(self.caches, src, dst)
                if self.dcaches is not None:
                    self.dcaches = self._copy_page_d(self.dcaches, src, dst)
            first = int(ent.first_token)
            self.stats["prefix_hits"] += 1
        else:
            if not self._reserve_pages(npg_req):
                return None
            pages = self.alloc.alloc(npg_req)
            npp = paging.pages_for_rows(S, P)    # pages the prompt covers
            if prefilled is not None:
                first, row = prefilled
                first = int(first)
            else:
                t0 = time.time()
                nxt, row = self._prefill(self.params, self.qparams,
                                         jnp.asarray(req.prompt)[None])
                first = int(jax.block_until_ready(nxt)[0])
                self.stats["prefill_s"] += time.time() - t0
                self.stats["prefills"] += 1
                self.stats["prefill_tokens"] += S
            phys = jnp.asarray(np.asarray(pages[:npp], np.int32))
            self.caches = self._insert_pages(self.caches, row,
                                             jnp.int32(slot), phys, npp)
            if self.draft is not None:
                t1 = time.time()
                drow = self._prefill_draft(self.draft.params,
                                           self.draft.qparams,
                                           jnp.asarray(req.prompt)[None])
                self.dcaches = self._insert_pages_d(self.dcaches, drow,
                                                    jnp.int32(slot), phys,
                                                    npp)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(self.dcaches)[0])
                self.stats["draft_prefill_s"] += time.time() - t1
                self.stats["draft_prefills"] += 1
                self.stats["draft_prefill_tokens"] += S
            if cache is not None:
                # register the prompt for sharing (best effort): the
                # cache takes its own refcount on the full pages and a
                # pristine device copy of the partial tail page — copied
                # *now*, before this owner's first decode write lands in
                # it. Sharing is whole-prompt-hash keyed: prefix K/V rows
                # are not bitwise stable across prefills of different
                # total lengths, identical prompts are (paging.py).
                tmpl = None
                if partial and self.alloc.can_alloc(1):
                    tmpl = self.alloc.alloc(1)[0]
                    src = jnp.int32(pages[n_full])
                    self.caches = self._copy_page(self.caches, src,
                                                  jnp.int32(tmpl))
                    if self.dcaches is not None:
                        self.dcaches = self._copy_page_d(self.dcaches, src,
                                                         jnp.int32(tmpl))
                if (n_full or tmpl is not None) and not (partial
                                                         and tmpl is None):
                    self.alloc.retain(pages[:n_full])
                    cache.insert(paging.PrefixEntry(
                        key=paging.prompt_key(req.prompt), prompt_len=S,
                        full_pages=tuple(pages[:n_full]), tail_page=tmpl,
                        first_token=first))

        pt_row = np.full((self.Lp,), paging.ZERO_PAGE, np.int32)
        pt_row[:len(pages)] = pages
        self.page_table[slot] = pt_row
        self.slot_pages[slot] = list(pages)
        self.pos[slot] = S
        self.last_tok[slot] = first
        self.stats["admitted"] += 1
        req.admit_t = time.time()
        req.tokens.append(first)
        req.slot = slot
        self.active[slot] = req
        return True

    def _finish(self, req: Request) -> None:
        req.finish_t = time.time()
        if req.slot >= 0:
            if self.paged:
                # eviction is a page release: refcounts drop, pages whose
                # last owner left go to the dirty quarantine (zeroed at
                # the next admission / drain), and the slot's table rows
                # point back at the trash page so its idle decode writes
                # can't touch live pages
                self.alloc.release(self.slot_pages[req.slot])
                self.slot_pages[req.slot] = []
                self.page_table[req.slot, :] = paging.TRASH_PAGE
                self.pos[req.slot] = 0
            self.active[req.slot] = None
            req.slot = -1
            self.stats["evicted"] += 1
        self.done[req.rid] = req

    def step(self) -> bool:
        """One engine iteration, shaped by the scheduler policy: the
        policy plans an ordered action tuple ("admit", "handoff",
        "prefill_chunk", "decode") and the engine executes it. The default
        OneShotScheduler plans ("admit", "decode") — the classic
        iteration, verbatim. Returns False when no action made progress
        (idle)."""
        progress = False
        for act in self.scheduler.plan_step(self):
            progress = bool(getattr(self, "_act_" + act)()) or progress
        return progress

    def _act_admit(self) -> bool:
        return self._admit() > 0

    def _act_decode(self) -> bool:
        """One batched decode over every active slot — or, with a draft
        attached, one speculative draft/verify round committing
        1..k_eff+1 tokens per slot."""
        if self.n_active == 0:
            return False
        if self.draft is not None:
            return self._spec_round()
        tok = jnp.asarray(self.last_tok)[:, None]
        pos = jnp.asarray(self.pos)
        t0 = time.time()
        if self.paged:
            nxt, self.caches = self._decode_paged(
                self.params, self.qparams, self.caches, tok, pos,
                jnp.asarray(self.page_table))
        else:
            nxt, self.caches = self._decode(self.params, self.qparams,
                                            self.caches, tok, pos)
        nxt = np.asarray(jax.block_until_ready(nxt))
        self.stats["decode_s"] += time.time() - t0
        self.stats["decode_steps"] += 1
        if self._prefill_job is not None:
            # the disaggregation liveness stat: decode batches that ran
            # while a prompt was mid-prefill. The one-shot engine's value
            # is identically zero — it cannot decode during a prefill.
            self.stats["decode_steps_mid_prefill"] += 1
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.stats["decode_tokens"] += 1
            req.tokens.append(int(nxt[slot]))
            self.last_tok[slot] = nxt[slot]
            self.pos[slot] += 1
            if req.done:
                self._finish(req)
        return True

    # ------------------------------------------------- chunked prefill path
    def _act_prefill_chunk(self) -> bool:
        """Advance the in-flight prefill by one chunk (starting a new job
        from the queue when none is in flight). A finished job moves to
        the handoff queue with its staged row cache and memoized first
        token; a paged prefix-cache hit skips staging entirely and hands
        off immediately."""
        if self._prefill_job is None:
            if not self.queue or len(self._handoff) >= self.max_slots:
                return False
            req = self.queue.popleft()
            if (self.paged and self.prefix_cache is not None
                    and self.prefix_cache.lookup(req.prompt) is not None):
                # hot prompt: pages and first token are already pinned —
                # no prefill work at all, _admit_paged redoes the lookup
                self._handoff.append((req, None, None))
                return True
            from repro.launch.scheduler import PrefillJob, chunk_plan
            self._prefill_job = PrefillJob(
                req=req, caches=self._fresh_row(),
                chunks=chunk_plan(int(req.prompt.size), self._chunk))
        job = self._prefill_job
        c = job.chunks.pop(0)
        toks = jnp.asarray(
            job.req.prompt[job.done_rows:job.done_rows + c])[None]
        t0 = time.time()
        nxt, job.caches = self._prefill_chunk(
            self.params, self.qparams, job.caches, toks,
            jnp.full((1,), job.done_rows, jnp.int32))
        first = int(jax.block_until_ready(nxt)[0])
        self.stats["prefill_s"] += time.time() - t0
        self.stats["prefill_chunks"] += 1
        job.done_rows += c
        if not job.chunks:
            job.first = first       # the request's first generated token
            self.stats["prefills"] += 1
            self.stats["chunked_prefills"] += 1
            self.stats["prefill_tokens"] += int(job.req.prompt.size)
            self._handoff.append((job.req, job.first, job.caches))
            self._prefill_job = None
        return True

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _act_handoff(self) -> bool:
        """Admit finished prefill jobs from the handoff queue into free
        slots — the KV handoff. A staged row inserts exactly like the
        one-shot path's fresh prefill row, so decode state is identical
        from the first step. Stops at the first entry that cannot place
        (no free slot / allocator pressure), preserving FIFO order."""
        progress = False
        if self.paged:
            self._flush_dirty()
        while self._handoff:
            req, first, row = self._handoff[0]
            if self.paged:
                slot = self._free_slot()
                if req.max_new_tokens > 1 and slot is None:
                    break
                got = self._admit_paged(
                    req, -1 if slot is None else slot,
                    prefilled=None if first is None else (first, row))
                if got is None:
                    break
            elif req.max_new_tokens == 1:
                # one-token request: the staged first token IS the answer
                self.stats["admitted"] += 1
                req.admit_t = time.time()
                req.tokens.append(int(first))
                self._finish(req)
            else:
                slot = self._free_slot()
                if slot is None:
                    break
                self._insert_staged(req, int(first), row, slot)
            self._handoff.popleft()
            progress = True
        return progress

    def _insert_staged(self, req: Request, first: int, row, slot: int
                       ) -> None:
        """Contiguous-arena tail of admission from a staged row cache:
        the one-shot path's post-prefill bookkeeping, reused verbatim by
        the handoff queue."""
        self.caches = self._insert(self.caches, row, jnp.int32(slot))
        if self.draft is not None:
            # the draft arena still prefills one-shot at handoff (its
            # sliced shapes make this the cheap half); chunking the draft
            # too would need a second staging row per job
            t1 = time.time()
            drow = self._prefill_draft(self.draft.params,
                                       self.draft.qparams,
                                       jnp.asarray(req.prompt)[None])
            self.dcaches = self._insert_d(self.dcaches, drow,
                                          jnp.int32(slot))
            jax.block_until_ready(
                jax.tree_util.tree_leaves(self.dcaches)[0])
            self.stats["draft_prefill_s"] += time.time() - t1
            self.stats["draft_prefills"] += 1
            self.stats["draft_prefill_tokens"] += int(req.prompt.size)
        self.stats["admitted"] += 1
        req.admit_t = time.time()
        req.tokens.append(first)
        self.pos[slot] = req.prompt.size
        self.last_tok[slot] = first
        req.slot = slot
        self.active[slot] = req

    def _spec_ks(self) -> list[int]:
        """Draft-window lengths the speculative path can dispatch at:
        {0} + powers of two <= draft_k — `_spec_round` quantizes to this
        set, so it is exactly the compiled-shape set `warmup` covers."""
        ks = [0]
        k = 1
        while k <= self.draft_k:
            ks.append(k)
            k *= 2
        return ks

    def _spec_round(self) -> bool:
        """One speculative draft/verify/commit round over active slots.

        k_eff = pow2_floor(min(draft_k, min remaining - 1)): the pow2
        floor keeps the compiled spec-step set bounded (`_spec_ks`, the
        warmup contract), and capping at min-remaining-1 guarantees every
        slot's k_eff+1 writes stay inside its [0, prompt+budget) arena
        prefix and its commits inside the token budget — the target's
        free token rides on top of at most k_eff accepted proposals, so a
        round commits at most `remaining` tokens and never truncates.
        k_eff = 0 (a slot is one token from done) degenerates to a plain
        one-token verify that still runs the draft scan once, keeping the
        draft arena in sync through the same code path."""
        from repro.launch.speculative import pow2_floor
        rem = min(req.max_new_tokens - len(req.tokens)
                  for req in self.active if req is not None)
        k = pow2_floor(min(self.draft_k, rem - 1))
        tok = jnp.asarray(self.last_tok)[:, None]
        pos = jnp.asarray(self.pos)
        t0 = time.time()
        if self.paged:
            tgt, ncm, self.caches, self.dcaches = self._spec_paged(
                self.params, self.qparams, self.draft.params,
                self.draft.qparams, self.caches, self.dcaches, tok, pos,
                jnp.asarray(self.page_table), k)
        else:
            tgt, ncm, self.caches, self.dcaches = self._spec(
                self.params, self.qparams, self.draft.params,
                self.draft.qparams, self.caches, self.dcaches, tok, pos, k)
        tgt = np.asarray(jax.block_until_ready(tgt))
        ncm = np.asarray(ncm)
        self.stats["decode_s"] += time.time() - t0
        # one round advances k+1 positions' worth of scoring in one
        # dispatch: decode_steps counts positions scored (slot_occupancy
        # keeps its meaning), decode_tokens counts only *committed*
        # tokens — drafted-but-rejected work shows up as the gap between
        # spec_drafted and spec_accepted, never as throughput
        self.stats["decode_steps"] += k + 1
        self.stats["spec_steps"] += 1
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            n = int(ncm[slot])
            self.stats["decode_tokens"] += n
            self.stats["spec_drafted"] += k
            self.stats["spec_accepted"] += n - 1
            req.tokens.extend(int(t) for t in tgt[slot, :n])
            self.last_tok[slot] = tgt[slot, n - 1]
            self.pos[slot] += n
            if req.done:
                self._finish(req)
        return True

    MAX_WINDOW = 32

    def warmed_window_ks(self) -> list[int]:
        """Window lengths `warmup()` precompiles: powers of two up to
        MAX_WINDOW. `_window` quantizes every dispatch to
        min(pow2_floor(remaining), MAX_WINDOW), so this set must cover
        everything reachable — the compile-set audit (repro.analysis)
        recomputes the reachable set independently and diffs it against
        this one."""
        ks, k = [], 1
        while k <= self.MAX_WINDOW:
            ks.append(k)
            k *= 2
        return ks

    def warmup(self) -> None:
        """Compile the decode dispatches on dummy inputs (slot state and
        caches untouched) so the first timed window measures decode, not
        XLA: every power-of-two window length (the `run()` path decodes
        exclusively through windows; the single-step `step()` path warms
        lazily on first use) plus the queued prompt lengths' prefills.
        With a draft attached, the speculative step compiles instead —
        one spec-step per k in `_spec_ks()` (the k_eff quantization
        guarantees no other shape can be dispatched) plus the draft's own
        prefills — so the compiled-shape set stays bounded either way.

        A chunked-prefill engine warms a different set: the single-step
        decode (its `run()` drives `step()`, never the window family) and
        one `_prefill_chunk` compile per bucket in
        `chunk_buckets(chunk)` — `chunk_plan`'s pow2 remainder
        decomposition guarantees no prompt length can dispatch any other
        chunk shape, so the compile set is bounded by the chunk size, not
        the workload's prompt lengths."""
        tok = jnp.zeros((self.max_slots, 1), jnp.int32)
        pos = jnp.zeros((self.max_slots,), jnp.int32)
        pt = jnp.asarray(self.page_table) if self.paged else None
        if self.draft is not None:
            for k in self._spec_ks():
                if self.paged:
                    tgt, _, _, _ = self._spec_paged(
                        self.params, self.qparams, self.draft.params,
                        self.draft.qparams, self.caches, self.dcaches,
                        tok, pos, pt, k)
                else:
                    tgt, _, _, _ = self._spec(
                        self.params, self.qparams, self.draft.params,
                        self.draft.qparams, self.caches, self.dcaches,
                        tok, pos, k)
                jax.block_until_ready(tgt)
        elif self._chunk:
            if self.paged:
                nxt, _ = self._decode_paged(self.params, self.qparams,
                                            self.caches, tok, pos, pt)
            else:
                nxt, _ = self._decode(self.params, self.qparams,
                                      self.caches, tok, pos)
            jax.block_until_ready(nxt)
        else:
            for k in self.warmed_window_ks():
                if self.paged:
                    toks, _ = self._decode_window_paged(
                        self.params, self.qparams, self.caches, tok, pos,
                        pt, k)
                else:
                    toks, _ = self._decode_window(self.params, self.qparams,
                                                  self.caches, tok, pos, k)
                jax.block_until_ready(toks)
        if self._chunk:
            from repro.launch.scheduler import chunk_buckets
            row = self._fresh_row()
            for c in chunk_buckets(self._chunk):
                nxt, row = self._prefill_chunk(
                    self.params, self.qparams, row,
                    jnp.zeros((1, c), jnp.int32), jnp.zeros((1,), jnp.int32))
                jax.block_until_ready(nxt)
            if self.draft is not None:
                for n in sorted({req.prompt.size for req in self.queue}):
                    drow = self._prefill_draft(
                        self.draft.params, self.draft.qparams,
                        jnp.zeros((1, int(n)), jnp.int32))
                    jax.block_until_ready(
                        jax.tree_util.tree_leaves(drow)[0])
            return
        # prefill compiles per distinct prompt length; the queued lengths
        # are known, so warm them here instead of inside _admit's timing
        for n in sorted({req.prompt.size for req in self.queue}):
            nxt, _ = self._prefill(self.params, self.qparams,
                                   jnp.zeros((1, int(n)), jnp.int32))
            jax.block_until_ready(nxt)
            if self.draft is not None:
                drow = self._prefill_draft(self.draft.params,
                                           self.draft.qparams,
                                           jnp.zeros((1, int(n)), jnp.int32))
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(drow)[0])

    def compile_cache_sizes(self) -> dict[str, int]:
        """Compiled-entry counts for every engine jit — the warmup
        contract's regression pin: after `warmup()` + `run()`, a chunked
        engine's `_prefill_chunk` count must equal
        `len(chunk_buckets(chunk))` and `_decode`/`_decode_paged` must
        stay at 1 (tests/test_scheduler.py asserts it), so a shape leak
        in the chunk plan can't silently recompile mid-serve."""
        out = {}
        for name in ("_prefill", "_prefill_chunk", "_insert", "_insert_d",
                     "_decode", "_decode_window", "_decode_paged",
                     "_decode_window_paged", "_insert_pages",
                     "_zero_pages", "_copy_page", "_spec", "_spec_paged",
                     "_prefill_draft"):
            fn = getattr(self, name, None)
            if fn is not None and hasattr(fn, "_cache_size"):
                out[name] = int(fn._cache_size())
        return out

    def entry_points(self) -> list[dict]:
        """The static-analysis registry (repro.analysis, DESIGN.md §4.13):
        every jitted dispatch the serve loop can reach for *this* engine's
        configuration, with example arguments at its real shapes and the
        out-sharding contract each must pin. Tracing an entry never runs
        device code (`jax.make_jaxpr` only), and the example rows/arrays
        are never inserted into live state.

        Each entry: name, fn (the jit), args (example tuple),
        static_argnums, expected_out (pytree of NamedShardings for
        arena/row-returning jits under TP — the same `kv_cache_specs` /
        replicated trees the constructor pinned — or None when unsharded
        or the output carries no arena)."""
        tok = jnp.zeros((self.max_slots, 1), jnp.int32)
        pos = jnp.zeros((self.max_slots,), jnp.int32)
        prompt = jnp.zeros((1, min(8, self.max_seq)), jnp.int32)
        rep, arena, row_sh = self._rep, self._arena_sh, self._row_sh
        tp = self.mesh is not None
        eps: list[dict] = []

        def add(name, fn, args, static=(), out=None):
            eps.append(dict(name=name, fn=fn, args=tuple(args),
                            static_argnums=tuple(static),
                            expected_out=out if tp else None))

        add("prefill", self._prefill, (self.params, self.qparams, prompt),
            out=(rep, row_sh))
        row = self._fresh_row()
        if self.paged:
            pt = jnp.asarray(self.page_table)
            npp = paging.pages_for_rows(int(prompt.shape[1]), self.page_size)
            phys = jnp.zeros((npp,), jnp.int32)
            ids = jnp.zeros((4,), jnp.int32)
            add("insert_pages", self._insert_pages,
                (self.caches, row, jnp.int32(0), phys, npp), static=(4,),
                out=arena)
            add("zero_pages", self._zero_pages, (self.caches, ids),
                out=arena)
            add("copy_page", self._copy_page,
                (self.caches, jnp.int32(1), jnp.int32(2)), out=arena)
            if self.draft is None:
                add("decode_paged", self._decode_paged,
                    (self.params, self.qparams, self.caches, tok, pos, pt),
                    out=(rep, arena))
                add("decode_window_paged", self._decode_window_paged,
                    (self.params, self.qparams, self.caches, tok, pos, pt,
                     2), static=(6,), out=(rep, arena))
        else:
            add("insert", self._insert, (self.caches, row, jnp.int32(0)),
                out=arena)
            if self.draft is None:
                add("decode", self._decode,
                    (self.params, self.qparams, self.caches, tok, pos),
                    out=(rep, arena))
                add("decode_window", self._decode_window,
                    (self.params, self.qparams, self.caches, tok, pos, 2),
                    static=(5,), out=(rep, arena))
        if self.draft is not None:
            from repro.launch.speculative import pow2_floor
            k = pow2_floor(self.draft_k)
            add("prefill_draft", self._prefill_draft,
                (self.draft.params, self.draft.qparams, prompt),
                out=self._drow_sh)
            drow = self.draft.lm.init_cache(1, self.max_seq,
                                            dtype=self._cache_dtype)
            if tp:
                drow = jax.device_put(drow, self._drow_sh)
            if self.paged:
                add("spec_paged", self._spec_paged,
                    (self.params, self.qparams, self.draft.params,
                     self.draft.qparams, self.caches, self.dcaches, tok,
                     pos, jnp.asarray(self.page_table), k), static=(9,),
                    out=(rep, rep, arena, self._darena_sh))
                add("insert_pages_d", self._insert_pages_d,
                    (self.dcaches, drow, jnp.int32(0), phys, npp),
                    static=(4,), out=self._darena_sh)
            else:
                add("spec", self._spec,
                    (self.params, self.qparams, self.draft.params,
                     self.draft.qparams, self.caches, self.dcaches, tok,
                     pos, k), static=(8,),
                    out=(rep, rep, arena, self._darena_sh))
                add("insert_d", self._insert_d,
                    (self.dcaches, drow, jnp.int32(0)),
                    out=self._darena_sh)
        if self._chunk:
            add("prefill_chunk", self._prefill_chunk,
                (self.params, self.qparams, self._fresh_row(),
                 jnp.zeros((1, self._chunk), jnp.int32),
                 jnp.zeros((1,), jnp.int32)), out=(rep, row_sh))
        return eps

    def _window(self) -> bool:
        """Admit, then decode up to the next scheduled eviction in one
        fused dispatch. Token-identical to repeated `step()` — the window
        length is the minimum remaining budget over active slots, so no
        admission opportunity is skipped."""
        if self.draft is not None:
            # the fused window scans one committed token per step per
            # slot; a speculative round commits 1..k_eff+1, so every
            # count-based event schedule in here would misfire
            raise RuntimeError(
                "speculative engines decode through step(): _window's "
                "event accounting assumes exactly one token per slot "
                "per step")
        if self._chunk:
            raise RuntimeError(
                "chunked-prefill engines decode through step(): a fused "
                "window cannot interleave prefill chunks — it would "
                "reintroduce the head-of-line block chunking removes")
        self._admit()
        if self.n_active == 0:
            return False
        k = min(req.max_new_tokens - len(req.tokens)
                for req in self.active if req is not None)
        # quantize to powers of two so the set of compiled window lengths
        # is bounded (and warmable) instead of one compile per workload
        k = min(1 << (k.bit_length() - 1), self.MAX_WINDOW)
        tok = jnp.asarray(self.last_tok)[:, None]
        pos = jnp.asarray(self.pos)
        t0 = time.time()
        if self.paged:
            toks, self.caches = self._decode_window_paged(
                self.params, self.qparams, self.caches, tok, pos,
                jnp.asarray(self.page_table), k)
        else:
            toks, self.caches = self._decode_window(
                self.params, self.qparams, self.caches, tok, pos, k)
        toks = np.asarray(jax.block_until_ready(toks))   # (k, slots)
        self.stats["decode_s"] += time.time() - t0
        self.stats["decode_steps"] += k
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.stats["decode_tokens"] += k
            req.tokens.extend(int(t) for t in toks[:, slot])
            self.last_tok[slot] = toks[-1, slot]
            self.pos[slot] += k
            if req.done:
                self._finish(req)
        return True

    def run(self) -> dict[int, np.ndarray]:
        """Drain the queue; returns rid -> generated tokens (prompt not
        included) for every request finished since the last drain, in rid
        order, and releases them — a long-lived engine stays bounded and
        a later drain never re-reports earlier batches. Decodes in
        event-free windows (one dispatch + one host sync per window);
        a speculative engine rounds through `step()` instead — each
        round already fuses k_eff+1 positions into one dispatch — and a
        chunked-prefill engine steps through `step()` so prefill chunks
        interleave with decode."""
        drive = (self.step if (self.draft is not None or self._chunk)
                 else self._window)
        while self.pending:
            if not drive() and (self.queue or self._handoff):
                raise RuntimeError("queue stuck with no active slots")
        if self.paged:
            # drain leaves no dirty quarantine behind: every released
            # page is zeroed and back on the free list
            self._flush_dirty()
        out = {rid: np.asarray(req.tokens, np.int32)
               for rid, req in sorted(self.done.items())}
        self.done.clear()
        return out

    def throughput(self) -> dict[str, float]:
        s = self.stats
        out = {
            "decode_tok_per_s": s["decode_tokens"] / max(s["decode_s"], 1e-9),
            "prefill_tok_per_s": (s["prefill_tokens"]
                                  / max(s["prefill_s"], 1e-9)),
            "slot_occupancy": (s["decode_tokens"]
                               / max(s["decode_steps"] * self.max_slots, 1)),
        }
        if self.draft is not None:
            # decode_tokens only ever counts committed tokens, so the
            # headline rate *is* accepted-tokens/s — the alias makes the
            # benchmark metric explicit
            out["accepted_tok_per_s"] = out["decode_tok_per_s"]
            out["acceptance_rate"] = (s["spec_accepted"]
                                      / max(s["spec_drafted"], 1))
        return out

    @staticmethod
    def _leaf_nbytes(leaf, per_device: bool) -> int:
        """Bytes of one array — per addressable shard when `per_device`
        (a TP-sharded leaf stores 1/tp of its rows on each device; a
        replicated leaf stores all of them everywhere)."""
        if per_device:
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                return int(shards[0].data.nbytes)
        return int(leaf.nbytes)

    def kv_bytes(self, per_device: bool = False) -> int:
        """KV bytes the engine is *using*. A pruned model's arena only
        holds rows for surviving kv heads / mamba channels / rwkv heads
        (LM.init_cache sizes from the SlimPlan shapes), so this shrinks
        with realized sparsity. A speculative engine's draft arena counts
        too — it is pinned HBM the serve needs, and excluding it
        under-reported every `--speculative` kv_bytes stat. Paged engines
        count only *allocated* pages (live + reserved) pro-rated over the
        pooled leaves, plus state leaves and the page table — the headline
        stat the ≥2x-concurrency bench leans on.

        `per_device` reports one device's share under TP: KV-head-sharded
        leaves weigh 1/tp, replicated fallbacks weigh full — the
        ~1/tp-shrink acceptance stat (tests/test_tp_engine.py)."""
        if not self.paged:
            leaves = jax.tree_util.tree_leaves(self.caches)
            if self.dcaches is not None:
                leaves += jax.tree_util.tree_leaves(self.dcaches)
            return sum(self._leaf_nbytes(lf, per_device) for lf in leaves)
        n_alloc = self.alloc.n_live + paging.N_RESERVED
        total = self.page_table.nbytes      # host numpy: replicated
        arenas = [self.caches]
        if self.dcaches is not None:
            arenas.append(self.dcaches)
        for caches in arenas:
            for key, leaf in caches.items():
                if (key.endswith(".k") or key.endswith(".v")
                        or key.endswith("_scale")):
                    total += (self._leaf_nbytes(leaf, per_device)
                              // self.n_pages) * n_alloc
                else:
                    # mamba/rwkv state: slot-sized
                    total += self._leaf_nbytes(leaf, per_device)
        return total

    def kv_pool_bytes(self) -> int:
        """KV bytes the engine *pins* in HBM regardless of load: the full
        pool(s) plus the page table. For a contiguous engine this equals
        kv_bytes(); for a paged engine it is the fixed budget that
        kv_bytes() draws against."""
        total = tree_bytes(self.caches)
        if self.dcaches is not None:
            total += tree_bytes(self.dcaches)
        if self.paged:
            total += self.page_table.nbytes
        return total

    def param_bytes(self, per_device: bool = False) -> int:
        """Bytes of the served param dict (codes + scales + dense rest).

        Counts the containers as served: a `--packed` engine's sub-byte
        word streams weigh their packed bytes, so this tracks
        `mean_bits` instead of flooring at the int8 container.
        `per_device` reports one device's share under TP (sharded leaves
        weigh 1/tp, replicated fallbacks weigh full)."""
        if not per_device:
            return tree_bytes(self.params)
        return sum(self._leaf_nbytes(lf, True)
                   for lf in jax.tree_util.tree_leaves(self.params))


# ----------------------------------------------------------------- drivers
def build_engine(arch: str, smoke: bool = True, *, quantized: bool = True,
                 compressed: bool = False, packed: bool = False,
                 pruned: bool = False, sparsity: float = 0.5,
                 keep_masks: dict | None = None, bits_init: float = 8.0,
                 max_slots: int = 4, max_seq: int = 64, seed: int = 0,
                 verbose: bool = False, speculative: bool = False,
                 draft_k: int = 4, draft_sparsity: float = 0.5,
                 draft_bits: float = 2.0, paged: bool = False,
                 page_size: int = 16, kv_bits: int | None = None,
                 n_pages: int | None = None,
                 prefix_sharing: bool = True, tp: int = 0,
                 prefill_chunk: int | None = None,
                 mesh=None) -> tuple[Engine, LM]:
    """Init an LM at `arch` scale and wrap it in an Engine.

    `pruned` serves the physically sliced subnet: `prepare_serving` builds
    keep masks (`keep_masks` from a GETA run, or magnitude masks at
    `sparsity`), materializes the sliced params, and installs the SlimPlan
    on `lm` — so this engine's decode dispatches, and its KV arena, run at
    the surviving widths. Passing `keep_masks` implies `pruned` (a mask
    dict that silently did nothing — or pruned under a dense label —
    would be worse than either behavior). Composes with `compressed`
    (int codes on pruned shapes) and `packed` (sub-byte word streams —
    implies `compressed`; `bits_init` sets the quantizer init width, so
    `bits_init=4` serves a genuinely 4-bit packed artifact).

    `speculative` attaches a self-speculative draft: the *same* init
    params sliced to `draft_sparsity` and packed at `draft_bits`
    (`launch/speculative.build_draft` — shared checkpoint, shared
    quantizer-init order, so the draft is GETA-calibrated to the target),
    decoding in draft/verify rounds of up to `draft_k` proposals. The
    output stream stays token-identical to the non-speculative engine —
    the `--speculative --smoke` parity check asserts it.

    `tp > 1` serves tensor-parallel over a (1, tp) device mesh
    (`make_tp_mesh`): params and KV arena shard per DESIGN.md §4.12, the
    token stream stays identical to tp=1. `prefill_chunk` swaps in a
    `ChunkedPrefillScheduler` so prefill interleaves with decode in
    `prefill_chunk`-row chunks. The two compose."""
    pruned = pruned or keep_masks is not None
    compressed = compressed or packed
    cfg = get_arch(arch, smoke=smoke)
    lm = LM(cfg)
    params, axes = lm.init(jax.random.PRNGKey(seed))
    draft = None
    if speculative:
        from repro.launch.speculative import build_draft
        # built from the same init params the target serves, *before*
        # prepare_serving resolves the target pair (the draft runs its
        # own prepare_serving on its own LM instance)
        draft = build_draft(arch, smoke, params, sparsity=draft_sparsity,
                            bits=draft_bits, seed=seed)
    params, qparams, meta = prepare_serving(
        lm, params, quantized=quantized, compressed=compressed,
        packed=packed, bits_init=bits_init, keep_masks=keep_masks,
        prune_sparsity=(sparsity if pruned and keep_masks is None else None))
    # an explicit `mesh` overrides tp — the static analyzer passes a
    # 1-device TP mesh so the sharding-pin audit runs on single-device CI
    if mesh is None and tp and tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)
    scheduler = None
    if prefill_chunk:
        from repro.launch.scheduler import ChunkedPrefillScheduler
        scheduler = ChunkedPrefillScheduler(chunk=int(prefill_chunk))
    eng = Engine(lm, params, qparams, max_slots=max_slots, max_seq=max_seq,
                 draft=draft, draft_k=draft_k, paged=paged,
                 page_size=page_size, kv_bits=kv_bits, n_pages=n_pages,
                 prefix_sharing=prefix_sharing, mesh=mesh, param_axes=axes,
                 scheduler=scheduler)
    meta["kv_bytes"] = eng.kv_bytes()
    if mesh is not None:
        meta["tp"] = {
            "devices": int(mesh.size),
            "param_bytes_per_device": eng.param_bytes(per_device=True),
            "kv_bytes_per_device": eng.kv_bytes(per_device=True),
            "replicated_fallbacks": sorted({n for n, _, _
                                            in eng.tp_fallbacks}),
        }
    if prefill_chunk:
        meta["prefill_chunk"] = int(prefill_chunk)
    if paged:
        meta["paged"] = {
            "page_size": int(eng.page_size),
            "n_pages": int(eng.n_pages),
            "kv_bits": eng.kv_bits,
            "kv_pool_bytes": eng.kv_pool_bytes(),
        }
    meta["decode_attn"] = model_layers.decode_attn_enabled()
    if draft is not None:
        meta["speculative"] = {
            "draft_k": int(draft_k),
            "draft_sparsity": float(draft.meta.get("sparsity", 0.0)),
            "draft_bits": float(draft_bits),
            "draft_param_bytes": tree_bytes(draft.params),
            "draft_kv_bytes": tree_bytes(eng.dcaches),
        }
    eng.serving_meta = meta
    if verbose and (compressed or pruned):
        print(compression_report(arch, meta))
    return eng, lm


def build_masked_reference_engine(arch: str, smoke: bool = True, *,
                                  sparsity: float = 0.5,
                                  quantized: bool = True, max_slots: int = 4,
                                  max_seq: int = 64, seed: int = 0
                                  ) -> tuple[Engine, LM]:
    """The pruned path's correctness oracle: the same model served dense
    and keep-all, with the same magnitude masks *multiplied in* instead of
    sliced away. Shares seed, masks and quantizer init with
    `build_engine(pruned=True)`, so decode must be token-identical — the
    CI smoke and `tests/test_slim_serving.py` assert exactly that."""
    from repro.core.subnet import masked_reference_params
    cfg = get_arch(arch, smoke=smoke)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(seed))
    masked, qparams = masked_reference_params(lm, params, sparsity,
                                              quantized=quantized)
    return Engine(lm, masked, qparams, max_slots=max_slots,
                  max_seq=max_seq), lm


def synthetic_prompts(cfg, prompt_lens: list[int], seed: int = 0
                      ) -> list[np.ndarray]:
    """Deterministic per-request prompts: request i is the first
    prompt_lens[i] tokens of row i of the synthetic LM stream — row j of
    `serve_loop`'s prompt matrix when lengths are equal, which is what the
    engine-vs-static parity test leans on."""
    mx = max(prompt_lens)
    mat = np.asarray(batch_for(cfg, seed, 0, len(prompt_lens), mx)["tokens"])
    return [mat[i, :n].astype(np.int32)
            for i, n in enumerate(prompt_lens)]


def engine_serve(arch: str, smoke: bool, prompt_lens: list[int], gen: int,
                 *, quantized: bool = True, compressed: bool = False,
                 packed: bool = False, pruned: bool = False,
                 sparsity: float = 0.5, bits_init: float = 8.0,
                 max_slots: int = 4, seed: int = 0, verbose: bool = True,
                 decode_attn: bool | None = None,
                 speculative: bool = False, draft_k: int = 4,
                 draft_sparsity: float = 0.5, draft_bits: float = 2.0,
                 paged: bool = False, page_size: int = 16,
                 kv_bits: int | None = None, tp: int = 0,
                 prefill_chunk: int | None = None,
                 stats: dict | None = None) -> dict[int, np.ndarray]:
    """Submit one request per prompt length, run to drain, report tok/s.

    `decode_attn` pins the fused flash-decode attention kernel on (True)
    or off (False) for this serve — build, warmup and drain all run under
    the override; None leaves the process default (on) untouched."""
    max_seq = max(prompt_lens) + gen
    ctx = (model_layers.use_decode_attn(decode_attn)
           if decode_attn is not None else contextlib.nullcontext())
    with ctx:
        eng, lm = build_engine(arch, smoke, quantized=quantized,
                               compressed=compressed, packed=packed,
                               pruned=pruned, sparsity=sparsity,
                               bits_init=bits_init, max_slots=max_slots,
                               max_seq=max_seq, seed=seed, verbose=verbose,
                               speculative=speculative, draft_k=draft_k,
                               draft_sparsity=draft_sparsity,
                               draft_bits=draft_bits, paged=paged,
                               page_size=page_size, kv_bits=kv_bits,
                               tp=tp, prefill_chunk=prefill_chunk)
        for p in synthetic_prompts(lm.cfg, prompt_lens, seed):
            eng.submit(p, gen)
        eng.warmup()
        out = eng.run()
    if stats is not None:
        stats.update(eng.stats, **eng.throughput(),
                     param_bytes=eng.param_bytes(), kv_bytes=eng.kv_bytes(),
                     kv_pool_bytes=eng.kv_pool_bytes())
    if verbose:
        th = eng.throughput()
        mode = "compressed" if (compressed or packed) else "dense"
        if packed:
            mode += "+packed"
        if pruned:
            mode += f"+pruned@{eng.serving_meta.get('sparsity', 0.0):.2f}"
        if speculative:
            sm = eng.serving_meta.get("speculative", {})
            mode += (f"+spec(k={sm.get('draft_k', draft_k)}, draft "
                     f"s{100 * sm.get('draft_sparsity', 0.0):.0f}/"
                     f"b{sm.get('draft_bits', draft_bits):.0f})")
        if paged:
            mode += "+paged"
            if kv_bits is not None:
                mode += f"@kv{kv_bits}"
        if tp and tp > 1:
            mode += f"+tp{tp}"
        if prefill_chunk:
            mode += f"+chunked@{prefill_chunk}"
        line = (f"{arch} [engine/{mode}]: {len(prompt_lens)} requests "
                f"({', '.join(str(n) for n in prompt_lens)} prompt tokens, "
                f"{gen} new each) on {max_slots} slots — "
                f"{eng.stats['decode_tokens']} decode tokens in "
                f"{eng.stats['decode_s']:.2f}s "
                f"({th['decode_tok_per_s']:.1f} tok/s, occupancy "
                f"{th['slot_occupancy']:.2f}); one-shot prefill "
                f"{th['prefill_tok_per_s']:.1f} tok/s")
        if speculative:
            line += (f"; acceptance {th['acceptance_rate']:.2f} over "
                     f"{eng.stats['spec_steps']} rounds")
        print(line)
    return out
